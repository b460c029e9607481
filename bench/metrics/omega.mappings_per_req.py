"""Solution mappings a request carries, over the requests that carry any
(Counters.mappings_sent / Counters.omega_requests)."""


def read(rec):
    c = rec.counters
    if not c.get("omega_requests"):
        return None
    return c["mappings_sent"] / c["omega_requests"]
