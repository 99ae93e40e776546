"""System behaviour: checkpoint/restore (incl. elastic), fault tolerance,
straggler mitigation, gradient compression, end-to-end training loop, and a
multi-device shard_map collective (subprocess with host devices)."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as dl
from repro.checkpoint import CheckpointManager
from repro.distributed import (FailureInjector, HostFailure,
                               StragglerDetector, run_resilient)
from repro.launch.train import Trainer, TrainJob
from repro.optim import (AdamW, compress_grads, cosine_schedule,
                         init_error_feedback)


# -------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_pytree():
    mgr = CheckpointManager(dl.MemoryProvider(), async_save=False)
    state = {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                        "b": jnp.ones((4,), jnp.bfloat16)},
             "opt": {"step": jnp.int32(7)}}
    mgr.save(state, step=7)
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    out = mgr.restore(like)
    np.testing.assert_array_equal(out["params"]["w"], state["params"]["w"])
    assert out["params"]["b"].dtype == jnp.bfloat16
    assert int(out["opt"]["step"]) == 7


def test_checkpoint_async_and_retention():
    mgr = CheckpointManager(dl.MemoryProvider(), keep=2, async_save=True)
    state = {"w": jnp.zeros((64,))}
    for s in (1, 2, 3):
        mgr.save({"w": jnp.full((64,), float(s))}, step=s)
    mgr.wait()
    assert mgr.latest_step() == 3
    assert mgr.saved_steps == [2, 3]
    like = {"w": jax.ShapeDtypeStruct((64,), jnp.float32)}
    out = mgr.restore(like, step=3)
    np.testing.assert_array_equal(out["w"], np.full((64,), 3.0))
    # checkpoints are Deep Lake commits: time-travel metadata exists
    assert any(n.message.startswith("step=") for n in mgr.ds.log())


def test_checkpoint_versioned_history_is_deeplake():
    mgr = CheckpointManager(dl.MemoryProvider(), async_save=False, keep=5)
    mgr.save({"w": jnp.zeros((8,))}, step=1)
    mgr.save({"w": jnp.ones((8,))}, step=2)
    # raw rows live in the 'leaves' tensor of a normal dataset
    assert "leaves" in mgr.ds.tensor_names
    assert len(mgr.ds["leaves"]) == 2


# ---------------------------------------------------------- fault tolerance
def test_straggler_detector_flags_and_mitigates():
    events = []
    det = StragglerDetector(threshold=2.0, patience=2,
                            on_straggler=lambda s, t, b: events.append(s))
    for s in range(10):
        det.observe(s, 0.1)
    fired = [det.observe(10, 0.5), det.observe(11, 0.5)]
    assert fired == [False, True]
    assert det.mitigations == 1 and events == [11]
    assert det.flagged_steps == [10, 11]


def test_failure_injector_fires_once():
    inj = FailureInjector(fail_at_steps=(3,))
    inj.check(2)
    with pytest.raises(HostFailure):
        inj.check(3)
    inj.check(3)  # second pass: already failed once, proceeds


def test_run_resilient_restarts():
    attempts = []

    def make_runner(_):
        def run():
            attempts.append(1)
            if len(attempts) < 3:
                raise HostFailure("boom")
            return 42
        return run

    out = run_resilient(make_runner, max_restarts=5)
    assert out == {"final_step": 42, "restarts": 2}


# ----------------------------------------------------- gradient compression
def test_grad_compression_error_feedback_converges():
    grads = {"w": jnp.asarray(np.random.default_rng(0)
                              .standard_normal((256,)), jnp.float32)}
    fb = init_error_feedback(grads)
    acc_raw = np.zeros((256,))
    acc_cmp = np.zeros((256,))
    for _ in range(50):
        g, fb = compress_grads(grads, fb)
        acc_raw += np.asarray(grads["w"])
        acc_cmp += np.asarray(g["w"])
    # error feedback: accumulated compressed grads track the true sum
    rel = np.abs(acc_cmp - acc_raw).max() / np.abs(acc_raw).max()
    assert rel < 0.02, rel


# -------------------------------------------------------------- end-to-end
def test_trainer_loss_decreases_and_checkpoints():
    job = TrainJob(arch="gemma-2b", steps=12, global_batch=4, seq_len=64,
                   checkpoint_every=6, num_docs=16, log_every=100)
    t = Trainer(job)
    out = t.run(restore=False)
    assert out["final_step"] == 12
    losses = [h["loss"] for h in out["history"]]
    assert losses[-1] < losses[0]
    assert t.ckpt.latest_step() == 12


def test_trainer_restores_after_failure():
    job = TrainJob(arch="gemma-2b", steps=10, global_batch=4, seq_len=64,
                   checkpoint_every=2, num_docs=16, fail_at=(5,),
                   log_every=100)
    ckpt = CheckpointManager(dl.MemoryProvider(), keep=3)
    t1 = Trainer(job, ckpt=ckpt)
    with pytest.raises(HostFailure):
        t1.run(restore=False)
    assert ckpt.latest_step() >= 4
    # restarted job: the transient fault doesn't re-fire (real-world restart)
    import dataclasses as dc
    job2 = dc.replace(job, fail_at=())
    t2 = Trainer(job2, ckpt=ckpt, data_ds=t1.data_ds)
    out = t2.run(restore=True)          # resumes from checkpoint
    assert out["final_step"] == 10
    first_resumed = out["history"][0]["step"] if out["history"] else 10
    assert first_resumed >= 4           # at most checkpoint_every recomputed


def test_trainer_with_tql_filter_and_compression():
    job = TrainJob(arch="granite-moe-1b-a400m", steps=4, global_batch=2,
                   seq_len=64, grad_compress=True, num_docs=12,
                   tql_filter="SELECT * FROM dataset WHERE doc_id % 2 == 0",
                   log_every=100)
    out = Trainer(job).run(restore=False)
    assert np.isfinite(out["final_loss"])


def test_job_config_cuts_depth_only():
    from repro.configs import get_arch, job_config
    full = get_arch("starcoder2-3b")
    cut = job_config("starcoder2-3b", smoke=False, num_layers=4)
    assert cut.num_layers == 4
    assert cut.with_(num_layers=full.num_layers) == full
    assert job_config("starcoder2-3b", smoke=False) == full
    with pytest.raises(ValueError):
        job_config("starcoder2-3b", smoke=True, num_layers=4)


def test_device_feeder_refuses_key_without_sharding():
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.data import DeviceFeeder
    from repro.launch.mesh import make_local_mesh
    sh = NamedSharding(make_local_mesh(), P())
    batch = {"tokens": np.zeros((2, 4), np.int32),
             "extra": np.zeros((2,), np.float32)}
    with pytest.raises(KeyError, match="extra"):
        next(iter(DeviceFeeder(iter([batch]), {"tokens": sh})))


def test_serve_generates_tokens():
    from repro.launch.serve import Server, ServeJob
    job = ServeJob(arch="gemma-2b", batch=2, prompt_len=8, max_new_tokens=6)
    srv = Server(job)
    prompts = np.random.default_rng(0).integers(
        0, srv.cfg.vocab_size, (2, 8)).astype(np.int32)
    out = srv.generate(prompts)
    assert out.shape == (2, 14)
    assert (out[:, :8] == prompts).all()
    assert (out[:, 8:] < srv.cfg.vocab_size).all()
    # greedy decode is deterministic
    out2 = Server(job).generate(prompts)
    np.testing.assert_array_equal(out, out2)


def test_serve_compiles_cache_init_once_per_size():
    from repro.launch.serve import Server, ServeJob
    job = ServeJob(arch="gemma-2b", batch=2, prompt_len=4, max_new_tokens=2)
    srv = Server(job)
    prompts = np.zeros((2, 4), np.int32)
    srv.generate(prompts)
    init = srv._cache_inits[(2, 6)]
    srv.generate(prompts)
    assert srv._cache_inits == {(2, 6): init}
    assert init._cache_size() == 1
    srv.generate(prompts, max_new_tokens=3)
    assert set(srv._cache_inits) == {(2, 6), (2, 7)}


# ---------------------------------------------- multi-device collective path
MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.collectives import make_quantized_allreduce
    from repro.launch.mesh import make_local_mesh

    mesh = jax.make_mesh((2, 4), ("pod", "data"))
    ar = make_quantized_allreduce(mesh, axis_name="pod")
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 16)),
                    jnp.float32)
    out = ar({"g": x})["g"]
    # out_specs P(None, ...) collapses the pod axis: (4, 16) mean over pods
    want = np.asarray(x).reshape(2, 4, 16).mean(axis=0)
    got = np.asarray(out)
    assert got.shape == want.shape, (got.shape, want.shape)
    err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert err < 0.05, err
    # elastic restore across meshes: save on 8-dev mesh, load on 4-dev view
    import repro.core as dl
    from repro.checkpoint import CheckpointManager
    from jax.sharding import NamedSharding
    mgr = CheckpointManager(dl.MemoryProvider(), async_save=False)
    big = jnp.asarray(np.arange(64, dtype=np.float32).reshape(8, 8))
    sharded = jax.device_put(big, NamedSharding(mesh, P(("pod", "data"), None)))
    mgr.save({"w": sharded}, step=1)
    mesh2 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
    sh2 = {"w": NamedSharding(mesh2, P("data", None))}
    out2 = mgr.restore({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                       shardings=sh2)
    np.testing.assert_array_equal(np.asarray(out2["w"]), np.asarray(big))
    assert out2["w"].sharding.num_devices == 4
    print("MULTIDEV_OK")
""")


def test_quantized_allreduce_and_elastic_restore_multidevice():
    r = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MULTIDEV_OK" in r.stdout


SHARDED_INIT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro.distributed import sharding_for_specs
    from repro.launch.steps import train_state_specs
    from repro.launch.train import Trainer, TrainJob

    t = Trainer(TrainJob(arch="gemma-2b", global_batch=4, seq_len=32,
                         num_docs=4))
    assert dict(t.mesh.shape) == {"data": 4, "model": 1}, t.mesh.shape
    state, step = t.initial_state(restore=False)
    assert step == 0
    want = sharding_for_specs(train_state_specs(t.model, t.opt), t.mesh,
                              t.rules)
    leaves = jax.tree_util.tree_leaves(state)
    for got, exp in zip(leaves, jax.tree_util.tree_leaves(want)):
        assert got.sharding.is_equivalent_to(exp, got.ndim), (got.sharding, exp)
    # FSDP: the embedding's d_model axis is split over the 4 devices
    emb = state["params"]["embed"]
    assert len(emb.sharding.device_set) == 4
    assert {s.data.shape for s in emb.addressable_shards} == {
        (emb.shape[0], emb.shape[1] // 4)}
    m = state["opt"]["m"]["embed"]
    assert m.sharding.is_equivalent_to(emb.sharding, m.ndim)
    print("SHARDED_INIT_OK")
""")


def test_trainer_initialises_state_in_fsdp_shardings():
    r = subprocess.run([sys.executable, "-c", SHARDED_INIT_SCRIPT],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_INIT_OK" in r.stdout
