"""The kernel the cells drive compiles for a TPU v5e at the cells' own
shape, against a described v5e:2x2 topology (no chip is needed): the
biosignal graph at 2048 / 512 with the features, margin and class outputs.
A compile that passes runs nothing.

The topology is described inside a fixture, never at import: only one
process may load the TPU library."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bench.registry import Registry

REG = Registry.from_root()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _shape(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _app(config):
    cfg = REG.config(config)
    mod = REG.module("apps", cfg["graph"])
    return cfg, mod.build(cfg, mod.params(cfg, np.random.default_rng(0)))


@pytest.mark.parametrize("config", ["mbiotracker"])
def test_stream_kernel_compiles_at_the_cell_shape(config, topo):
    from repro.kernels.pipeline.graph import (get_graph_factory,
                                              graph_stream_pallas,
                                              ring_chunk_samples)
    one = SingleDeviceSharding(topo.devices[0])
    cfg, app = _app(config)
    st = cfg["stream"]
    graph, ops = get_graph_factory(cfg["graph"])(app)
    ops = [_shape(o.shape, one, o.dtype) for o in ops]
    n = ring_chunk_samples(st["window"], st["hop"], st["batch_windows"])
    compiled = jax.jit(lambda x, *o: graph_stream_pallas(
        x, o, graph=graph, window=st["window"], hop=st["hop"],
        interpret=False, outputs=tuple(st["outputs"]))).lower(
            _shape((n,), one), *ops).compile()
    assert "tpu_custom_call" in compiled.as_text()
