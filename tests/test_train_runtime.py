"""Training runtime tests: optimizer, checkpointing, failure recovery,
gradient compression, brTPF data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from repro.train import checkpoint as ckpt
from repro.train.grad_compress import (compress_with_feedback,
                                       compressed_psum_tree, dequantize,
                                       init_error_state, quantize)
from repro.train.loop import Trainer, TrainerConfig
from repro.train.optimizer import (AdamW, apply_updates, constant_lr,
                                   warmup_cosine)


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

class TestAdamW:
    def test_converges_on_quadratic(self):
        opt = AdamW(learning_rate=constant_lr(0.1), weight_decay=0.0)
        params = {"w": jnp.array([5.0, -3.0])}
        state = opt.init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            updates, state, _ = opt.update(grads, state, params)
            params = apply_updates(params, updates)
        assert float(jnp.abs(params["w"]).max()) < 1e-2

    def test_clip_norm(self):
        opt = AdamW(learning_rate=constant_lr(0.1), clip_norm=1.0)
        params = {"w": jnp.zeros((4,))}
        state = opt.init(params)
        _, _, metrics = opt.update({"w": jnp.full((4,), 100.0)}, state,
                                   params)
        assert float(metrics["grad_norm"]) > 1.0  # pre-clip norm reported

    def test_schedule_warmup_cosine(self):
        sched = warmup_cosine(1.0, 10, 100)
        assert float(sched(jnp.int32(0))) == 0.0
        assert abs(float(sched(jnp.int32(10))) - 1.0) < 1e-6
        assert float(sched(jnp.int32(100))) < 0.2


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (8, 4)),
            "nested": {"b": jnp.arange(6, dtype=jnp.int32)}}


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = _tree()
        ckpt.save(str(tmp_path), 7, tree)
        step, restored = ckpt.restore(str(tmp_path), tree)
        assert step == 7
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), tree, restored)

    def test_partial_write_ignored(self, tmp_path):
        tree = _tree()
        ckpt.save(str(tmp_path), 1, tree)
        # simulate a crash mid-write: directory without COMMIT
        bad = tmp_path / "step_00000002"
        bad.mkdir()
        (bad / "manifest.json").write_text("{}")
        assert ckpt.latest_step(str(tmp_path)) == 1

    def test_corrupt_falls_back(self, tmp_path):
        tree = _tree()
        ckpt.save(str(tmp_path), 1, tree)
        ckpt.save(str(tmp_path), 2, tree)
        # corrupt the newest: truncate a leaf
        leaf = tmp_path / "step_00000002" / "leaf_00000.npy"
        leaf.write_bytes(leaf.read_bytes()[:16])
        step, _ = ckpt.restore(str(tmp_path), tree)
        assert step == 1

    def test_cleanup_keeps_n(self, tmp_path):
        tree = _tree()
        for s in range(5):
            ckpt.save(str(tmp_path), s, tree)
        ckpt.cleanup(str(tmp_path), keep=2)
        assert ckpt.valid_steps(str(tmp_path)) == [3, 4]

    def test_async_checkpointer(self, tmp_path):
        tree = _tree()
        ac = ckpt.AsyncCheckpointer(str(tmp_path), keep=2)
        ac.save(3, tree)
        ac.wait()
        assert ckpt.latest_step(str(tmp_path)) == 3

    def test_resharding_restore(self, tmp_path):
        """Elastic path: restore with explicit (single-device) shardings."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        tree = _tree()
        ckpt.save(str(tmp_path), 1, tree)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
        step, restored = ckpt.restore(str(tmp_path), tree, sh)
        assert step == 1
        assert all(isinstance(x, jax.Array)
                   for x in jax.tree.leaves(restored))


# ---------------------------------------------------------------------------
# Trainer: failure recovery + stragglers
# ---------------------------------------------------------------------------

def _toy_setup(tmp_path, total=30, ckpt_every=5):
    from repro.train.optimizer import AdamW, constant_lr

    opt = AdamW(learning_rate=constant_lr(0.05), weight_decay=0.0)
    params = {"w": jnp.array(4.0)}
    opt_state = opt.init(params)

    @jax.jit
    def step_fn(params, opt_state, batch):
        def loss_fn(p):
            return jnp.square(p["w"] - batch["target"]).sum()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state, _ = opt.update(grads, opt_state, params)
        return (apply_updates(params, updates), opt_state,
                {"loss": loss})

    cfg = TrainerConfig(total_steps=total, ckpt_dir=str(tmp_path),
                        ckpt_every=ckpt_every, max_restarts=3)
    return cfg, step_fn, params, opt_state


def _data():
    while True:
        yield {"target": jnp.array(1.0)}


class TestTrainer:
    def test_runs_and_learns(self, tmp_path):
        cfg, step_fn, params, opt_state = _toy_setup(tmp_path)
        tr = Trainer(cfg, step_fn, params, opt_state)
        report = tr.train(_data())
        assert report.steps_run == 30
        assert report.final_loss < report.losses[0]

    def test_failure_recovery_resumes_from_checkpoint(self, tmp_path):
        cfg, step_fn, params, opt_state = _toy_setup(tmp_path)
        fired = {"done": False}

        def failure_hook(step):
            if step == 17 and not fired["done"]:
                fired["done"] = True
                raise RuntimeError("simulated node failure")

        tr = Trainer(cfg, step_fn, params, opt_state,
                     failure_hook=failure_hook)
        report = tr.train(_data())
        assert report.restarts == 1
        # resumed from the step-15 checkpoint and completed all 30 steps
        assert tr.step == 30
        # replayed steps 15..17 after the restore
        assert report.steps_run > 30
        assert report.final_loss < report.losses[0]

    def test_too_many_failures_raises(self, tmp_path):
        cfg, step_fn, params, opt_state = _toy_setup(tmp_path)

        def always_fail(step):
            raise RuntimeError("dead node")

        tr = Trainer(cfg, step_fn, params, opt_state,
                     failure_hook=always_fail)
        with pytest.raises(RuntimeError):
            tr.train(_data())

    def test_resume_across_trainer_instances(self, tmp_path):
        cfg, step_fn, params, opt_state = _toy_setup(tmp_path, total=10)
        tr = Trainer(cfg, step_fn, params, opt_state)
        tr.train(_data())
        # "process restart": a new trainer picks up at step 10's ckpt
        cfg2, step_fn2, params2, opt_state2 = _toy_setup(tmp_path,
                                                         total=20)
        tr2 = Trainer(cfg2, step_fn2, params2, opt_state2)
        assert tr2.try_resume()
        assert tr2.step == 10
        report = tr2.train(_data())
        assert tr2.step == 20 and report.steps_run == 10


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

class TestCompression:
    def test_quantize_error_bounded(self):
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
        q, scale = quantize(g)
        err = np.abs(np.asarray(dequantize(q, scale) - g))
        assert err.max() <= float(scale) * 0.5 + 1e-6

    def test_error_feedback_reduces_bias(self):
        """With error feedback, the *accumulated* dequantized signal
        tracks the accumulated gradient far better than without."""
        rng = np.random.default_rng(1)
        g_seq = [jnp.asarray(rng.normal(size=(64,)) * 0.01, jnp.float32)
                 for _ in range(50)]
        err = jnp.zeros((64,), jnp.float32)
        acc_fb = np.zeros(64)
        acc_nofb = np.zeros(64)
        acc_true = np.zeros(64)
        for g in g_seq:
            q, s, err = compress_with_feedback(g, err)
            acc_fb += np.asarray(dequantize(q, s))
            q2, s2 = quantize(g)
            acc_nofb += np.asarray(dequantize(q2, s2))
            acc_true += np.asarray(g)
        err_fb = np.abs(acc_fb - acc_true).mean()
        err_nofb = np.abs(acc_nofb - acc_true).mean()
        assert err_fb <= err_nofb + 1e-9

    def test_compressed_psum_single_device(self):
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        grads = {"w": jnp.asarray(np.random.default_rng(2).normal(
            size=(32,)), jnp.float32)}
        errs = init_error_state(grads)

        def fn(g, e):
            return compressed_psum_tree(g, e, "data")

        out, new_e = shard_map(
            fn, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)(grads, errs)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(grads["w"]), atol=2e-2)


# ---------------------------------------------------------------------------
# brTPF data pipeline
# ---------------------------------------------------------------------------

class TestDataPipeline:
    def test_selection_and_batches(self):
        from repro.data.pipeline import BrTPFDataPipeline, SyntheticCorpus
        corpus = SyntheticCorpus.generate(num_docs=100, vocab_size=512,
                                          seed=3)
        pipe = BrTPFDataPipeline(
            corpus, "?d hasDomain code\n?d hasQuality q0",
            batch_size=4, seq_len=32)
        assert pipe.stats.selected_docs > 0
        assert pipe.stats.num_requests > 0
        it = iter(pipe)
        b = next(it)
        assert b["tokens"].shape == (4, 32)
        assert b["targets"].shape == (4, 32)
        # next-token alignment
        np.testing.assert_array_equal(b["tokens"][:, 1:],
                                      b["targets"][:, :-1])
        # selected docs actually satisfy the query
        d = corpus.dictionary
        dom = d.lookup("hasDomain")
        code = d.lookup("code")
        for doc in pipe.selected_docs:
            assert corpus.store.contains(
                np.array([doc, dom, code], np.int32))

    def test_empty_selection_raises(self):
        from repro.data.pipeline import BrTPFDataPipeline, SyntheticCorpus
        corpus = SyntheticCorpus.generate(num_docs=20, seed=4)
        corpus.dictionary.intern("nonexistent")
        with pytest.raises(ValueError):
            BrTPFDataPipeline(corpus, "?d hasDomain nonexistent",
                              batch_size=2, seq_len=16)
