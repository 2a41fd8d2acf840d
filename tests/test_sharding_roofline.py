"""Sharding rules + HLO cost analyzer unit tests (no 512-device mesh —
the production meshes are exercised by launch/dryrun.py)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis.hlo_cost import analyze, replica_groups, type_bytes
from repro.launch.mesh import make_mesh
from repro.sharding.rules import Strategy, spec_for


class FakeMesh:
    axis_names = ("pod", "data", "model")

    class devices:
        shape = (2, 16, 16)


class FakeMesh2D:
    axis_names = ("data", "model")

    class devices:
        shape = (16, 16)


def test_spec_for_train_weights():
    st = Strategy("train")
    m = FakeMesh2D()
    # mlp weight: embed->data (FSDP), mlp->model (TP)
    assert spec_for(("embed", "mlp"), (4096, 14336), m, st) == \
        P("data", "model")
    # head-count not divisible and not padded here: heads dim replicated
    assert spec_for(("embed", "heads", "head_dim"), (4096, 56, 128), m, st) \
        == P("data", None, None)
    # padded head count shards
    assert spec_for(("embed", "heads", "head_dim"), (4096, 64, 128), m, st) \
        == P("data", "model", None)
    # whisper vocab 51865 does not divide 16 -> falls to embed/data
    assert spec_for(("vocab", "embed"), (51865, 1024), m, st) == \
        P(None, "data")


def test_spec_for_serve_cache():
    st = Strategy("serve")
    m = FakeMesh2D()
    # kv divisible: heads take model, batch takes data
    assert spec_for(("batch", "seq", "kv_heads", "head_dim"),
                    (128, 32768, 16, 64), m, st) == \
        P("data", None, "model", None)
    # kv = 8 < 16: sequence-sharded cache (flash-decoding layout)
    assert spec_for(("batch", "seq", "kv_heads", "head_dim"),
                    (128, 32768, 8, 128), m, st) == \
        P("data", "model", None, None)
    # long-context batch=1: seq grabs model, data idle for batch
    assert spec_for(("batch", "seq", "kv_heads", "head_dim"),
                    (1, 524288, 8, 120), m, st) == \
        P(None, "model", None, None)
    # serve weights: replicated over data (no FSDP gather at decode)
    assert spec_for(("embed", "mlp"), (4096, 14336), m, st) == \
        P(None, "model")


def test_spec_for_multipod_batch():
    st = Strategy("train")
    assert spec_for(("batch", None), (256, 4096), FakeMesh(), st) == \
        P(("pod", "data"), None)


# ---------------- HLO cost analyzer ----------------

_HLO = """
HloModule test

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,128] get-tuple-element(%p), index=1
  %w = f32[128,128] constant({...})
  %dot.1 = f32[8,128] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,128] all-reduce(%dot.1), replica_groups=[2,4]<=[4,2]T(1,0), to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,128]) tuple(%ni, %ar)
}

%cond (p: (s32[], f32[8,128])) -> pred[] {
  %p = (s32[], f32[8,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(24)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,128]) -> f32[8,128] {
  %a = f32[8,128] parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,128]) tuple(%z, %a)
  %w = (s32[], f32[8,128]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"24"}}
  ROOT %out = f32[8,128] get-tuple-element(%w), index=1
}
"""


def test_hlo_cost_multiplies_while_bodies():
    r = analyze(_HLO)
    # one dot = 2*8*128*128 flops, x24 trips
    assert r["flops"] == 24 * 2 * 8 * 128 * 128
    assert r["collectives"]["all-reduce"]["count"] == 24
    assert r["collectives"]["all-reduce"]["bytes"] == 24 * 8 * 128 * 4
    assert r["collectives"]["all-reduce"]["group_size"] == 4


def test_replica_group_reconstruction():
    g = replica_groups('replica_groups=[2,4]<=[4,2]T(1,0)')
    assert g.shape == (2, 4)
    ids = np.arange(8).reshape(4, 2).transpose(1, 0).reshape(2, 4)
    np.testing.assert_array_equal(g, ids)
    g2 = replica_groups('replica_groups={{0,2},{1,3}}')
    np.testing.assert_array_equal(g2, [[0, 2], [1, 3]])


def test_type_bytes():
    assert type_bytes("f32[8,128]") == 8 * 128 * 4
    assert type_bytes("(bf16[2,2]{1,0}, s8[16])") == 8 + 16
    assert type_bytes("pred[]") == 1


def test_analyzer_on_real_compiled_module(rng):
    """Compile a scanned matmul on CPU; analyzer flops must scale with the
    trip count while XLA's builtin count stays flat."""
    w = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))

    def f(x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    comp = jax.jit(f).lower(jnp.ones((8, 64))).compile()
    r = analyze(comp.as_text())
    expected = 10 * 2 * 8 * 64 * 64
    assert 0.9 * expected <= r["flops"] <= 1.2 * expected, r["flops"]


def test_spec_for_fsdp_strategy():
    """Pure-FSDP layout: batch over every axis, weights fully sharded."""
    st = Strategy("fsdp")
    m = FakeMesh2D()
    assert spec_for(("batch", None), (256, 4096), m, st) == \
        P(("data", "model"), None)
    # batch that can't span 256 falls back to data only
    assert spec_for(("batch", None), (32, 4096), m, st) == P("data", None)
    assert spec_for(("embed", "mlp"), (4096, 14336), m, st) == \
        P("data", "model")


def test_activation_specs_strategies():
    import jax
    from repro.sharding.ctx import make_activation_specs

    mesh = make_mesh((1, 1), ("data", "model"))
    tp = make_activation_specs(mesh, "train")
    assert tp["btd"].spec == P("data", None, None)
    assert tp["btv"].spec == P("data", None, "model")
    fs = make_activation_specs(mesh, "fsdp")
    assert fs["btd"].spec == P(("data", "model"), None, None)
    assert fs["btv"].spec == P(("data", "model"), None, None)
