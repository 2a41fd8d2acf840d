"""Optimizer, data pipeline, checkpointing, compression, fault logic."""
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import ckpt
from repro.data.pipeline import DataConfig, ShardedLoader
from repro.launch.mesh import make_mesh
from repro.runtime.fault import (HeartbeatMonitor, StragglerDetector,
                                 Supervisor, elastic_plan)
from repro.train import optim
from repro.train.compress import (EFCompressor, dequantize_block_int8,
                                  quantize_block_int8)


# ---------------- optimizer ----------------

@pytest.mark.parametrize("v_dtype", [jnp.float32, "qint8"])
@pytest.mark.parametrize("m_dtype", [jnp.float32, jnp.bfloat16])
def test_adamw_converges_quadratic(v_dtype, m_dtype):
    oc = optim.OptConfig(lr=0.1, warmup_steps=1, total_steps=200,
                         weight_decay=0.0, m_dtype=m_dtype, v_dtype=v_dtype)
    target = jnp.asarray(np.linspace(-2, 2, 64, dtype=np.float32)).reshape(8, 8)
    params = {"w": jnp.zeros((8, 8))}
    state = optim.init_opt_state(params, oc)
    for _ in range(150):
        grads = {"w": 2 * (params["w"] - target)}
        params, state, _ = optim.adamw_update(grads, state, params, oc)
    err = float(jnp.abs(params["w"] - target).max())
    assert err < 0.05, err


def test_schedule_shape():
    oc = optim.OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                         min_lr_ratio=0.1)
    s = [float(optim.schedule(jnp.asarray(t), oc)) for t in range(101)]
    assert s[0] < 0.2 and abs(s[10] - 1.0) < 1e-5
    assert s[100] == pytest.approx(0.1, rel=1e-3)
    assert all(a >= b - 1e-9 for a, b in zip(s[10:], s[11:]))  # monotone


def test_grad_clip():
    g = {"a": jnp.full((10,), 100.0)}
    clipped, gn = optim.clip_by_global_norm(g, 1.0)
    assert float(gn) > 100
    assert float(optim.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


# ---------------- data pipeline ----------------

def test_data_determinism_and_sharding():
    dc = DataConfig(vocab_size=97, seq_len=16, global_batch=8)
    full = ShardedLoader(dc, 0, 1).batch(3)
    shards = [ShardedLoader(dc, h, 4).batch(3) for h in range(4)]
    merged = np.concatenate([s["tokens"] for s in shards])
    np.testing.assert_array_equal(merged, full["tokens"])
    again = ShardedLoader(dc, 0, 1).batch(3)
    np.testing.assert_array_equal(again["tokens"], full["tokens"])
    assert full["tokens"].max() < 97 and full["tokens"].min() >= 0
    # labels are next tokens
    np.testing.assert_array_equal(full["labels"][:, :-1],
                                  full["tokens"][:, 1:])


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000), st.integers(0, 1000))
def test_data_steps_differ(s1, s2):
    dc = DataConfig(vocab_size=1000, seq_len=32, global_batch=2)
    l = ShardedLoader(dc)
    if s1 != s2:
        assert not np.array_equal(l.batch(s1)["tokens"],
                                  l.batch(s2)["tokens"])


# ---------------- checkpoint ----------------

def test_checkpoint_roundtrip_and_resume():
    d = tempfile.mkdtemp()
    try:
        state = {"params": {"w": jnp.arange(12.0).reshape(3, 4)},
                 "opt": {"m": [jnp.ones(3), jnp.zeros(2)]},
                 "step": jnp.asarray(7)}
        ckpt.save(state, 7, d)
        ckpt.save(state, 9, d)
        assert ckpt.latest_step(d) == 9
        out = ckpt.restore(d, 9, state)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        shutil.rmtree(d)


def test_checkpoint_async_and_reshard():
    d = tempfile.mkdtemp()
    try:
        state = {"w": jnp.arange(64.0).reshape(8, 8)}
        _, t = ckpt.save(state, 1, d, async_write=True)
        t.join()
        mesh = make_mesh((1, 1), ("data", "model"))
        sh = {"w": jax.NamedSharding(mesh, jax.sharding.PartitionSpec(
            "data", None))}
        out = ckpt.restore(d, 1, state, sh)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(state["w"]))
        assert out["w"].sharding.spec == sh["w"].spec
    finally:
        shutil.rmtree(d)


# ---------------- compression ----------------

@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 400))
def test_int8_quant_error_bound(seed, n):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=n).astype(np.float32)) * 10
    q, s = quantize_block_int8(x, block=64)
    deq = dequantize_block_int8(q, s, x.shape)
    blockmax = np.abs(np.asarray(x)).max()
    assert float(jnp.abs(deq - x).max()) <= blockmax / 127.0 + 1e-6


def test_error_feedback_reduces_bias(rng):
    grads = {"w": jnp.asarray(rng.normal(size=(256,)).astype(np.float32))}
    c = EFCompressor(block=64)
    res = c.init(grads)
    acc_plain = np.zeros(256)
    acc_ef = np.zeros(256)
    for _ in range(50):
        comp, res = c.compress(grads, res)
        acc_ef += np.asarray(c.decompress(comp, grads)["w"])
        q, s = quantize_block_int8(grads["w"], 64)
        acc_plain += np.asarray(dequantize_block_int8(q, s, (256,)))
    true = np.asarray(grads["w"]) * 50
    assert np.abs(acc_ef - true).max() <= np.abs(acc_plain - true).max() + 1e-4
    assert np.abs(acc_ef - true).max() < 0.2


# ---------------- fault tolerance ----------------

def test_heartbeat_and_stragglers():
    hb = HeartbeatMonitor(timeout_s=10)
    hb.beat(0, t=100.0)
    hb.beat(1, t=105.0)
    assert hb.dead(now=112.0) == [0]
    assert hb.alive(now=112.0) == [1]

    det = StragglerDetector(straggler_factor=2.0, evict_after=2)
    for step in range(10):
        for w in range(4):
            det.record(w, 1.0 if w != 3 else 5.0)
        det.stragglers()
    assert det.stragglers() == [3]


@settings(max_examples=25, deadline=None)
@given(st.integers(16, 4096))
def test_elastic_plan_invariants(chips):
    plan = elastic_plan(chips, model_axis=16, pods_of=256)
    assert plan["chips"] <= chips
    assert plan["model"] == 16
    assert plan["data"] & (plan["data"] - 1) == 0      # power of two
    assert plan["chips"] == plan["pod"] * plan["data"] * plan["model"]


def test_supervisor_recovers_from_failures():
    store = {}

    def save_fn(state, step):
        store[step] = float(state)

    def restore_fn(step):
        return jnp.asarray(store.get(step, 0.0))

    failures = {7, 15}

    def inject(step):
        if step in failures:
            failures.discard(step)
            raise RuntimeError("node lost")

    def step_fn(state, batch):
        return state + batch, {"loss": state}

    sup = Supervisor(save_fn=save_fn, restore_fn=restore_fn, ckpt_every=5)
    save_fn(jnp.asarray(0.0), 0)
    state, step, _ = sup.run(jnp.asarray(0.0), step_fn,
                             lambda s: jnp.asarray(1.0), 20,
                             inject_failure=inject)
    assert step == 20
    assert float(state) == 20.0      # deterministic replay => exact result


def test_psum_compressed_shard_map(rng):
    """Compressed all-reduce building block under shard_map (1 device)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.train.compress import psum_compressed

    mesh = make_mesh((1,), ("pod",))
    x = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    f = jax.shard_map(lambda v: psum_compressed(v, "pod"), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
    with mesh:
        y = f(x)
    # single member: psum is identity up to int8 quantization error
    assert float(jnp.abs(y - x).max()) <= float(jnp.abs(x).max()) / 127 + 1e-6
