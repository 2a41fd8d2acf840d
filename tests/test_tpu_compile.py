"""Every kernel of the served path compiles for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached, so each test
lowers a kernel with ``interpret=False`` against a described ``v5e:2x2``
topology and compiles it for one of its chips at the real widths: the
biosignal graph at 2048 / 512 (also inside the stream's per-upload loop
at the archive length), the ASR graph at 512 / 128, and the standalone
FIR, FFT and RoPE kernels. A compile that passes says the
kernel is Mosaic-legal (tiling, VMEM, lowering rules); it runs nothing.
Each compiled program must hold the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fft.kernel import fft_pallas
from repro.kernels.fir.kernel import fir_pallas
from repro.kernels.pipeline.graph import (default_app, get_graph_factory,
                                          graph_pallas, graph_ring_pallas,
                                          graph_stream_pallas,
                                          ring_chunk_samples)
from repro.kernels.rope.kernel import rope_pallas
from repro.serve.stream import _upload_loop

GRAPHS = {"biosignal": (2048, 512), "asr": (512, 128)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _graph(name, sharding):
    graph, operands = get_graph_factory(name)(default_app(name))
    return graph, [_shape(o.shape, sharding, o.dtype) for o in operands]


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_graph_framed_compiles(name, one_chip):
    window, _ = GRAPHS[name]
    graph, ops = _graph(name, one_chip)
    _assert_mosaic(lambda x, *o: graph_pallas(x, o, graph=graph,
                                              interpret=False),
                   _shape((8, window), one_chip), *ops)


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_graph_stream_compiles(name, one_chip):
    window, hop = GRAPHS[name]
    graph, ops = _graph(name, one_chip)
    n_samples = ring_chunk_samples(window, hop, 64)
    _assert_mosaic(lambda x, *o: graph_stream_pallas(
        x, o, graph=graph, window=window, hop=hop, interpret=False),
        _shape((n_samples,), one_chip), *ops)


def test_biosignal_ring_compiles(one_chip):
    window, hop = GRAPHS["biosignal"]
    graph, ops = _graph("biosignal", one_chip)
    span = ring_chunk_samples(window, hop, 64)
    _assert_mosaic(lambda x, *o: graph_ring_pallas(
        x, o, graph=graph, window=window, hop=hop, interpret=False),
        _shape((4, span), one_chip), *ops)


def test_biosignal_upload_loop_compiles(one_chip):
    """`BiosignalStream.process`'s per-upload program at the archive
    length (8 h at 64 Hz): the stream kernel runs inside the loop's body,
    under the name the device trace gives its launches."""
    window, hop = GRAPHS["biosignal"]
    graph, ops = _graph("biosignal", one_chip)
    text = jax.jit(lambda x, *o: _upload_loop(
        x, o, graph=graph, window=window, hop=hop, batch_windows=64,
        interpret=False, block_frames=None,
        outputs=("features", "margin", "class"))).lower(
        _shape((1_843_200,), one_chip), *ops).compile().as_text()
    comps = {m.group(1): m.group(0) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \(.*?^}", text, re.M | re.S)}
    (body,) = re.findall(r"body=%([\w.\-]+)", text)
    reached, todo = set(), [body]
    while todo:
        name = todo.pop()
        if name not in reached:
            reached.add(name)
            todo += re.findall(r"calls=%([\w.\-]+)", comps[name])
    assert any("tpu_custom_call" in comps[c] for c in reached)
    assert re.search(r"^\s*%graph_stream_kernel\.biosignal[.\d]* = ",
                     comps[body], re.M)


def test_fir_kernel_compiles(one_chip):
    _assert_mosaic(lambda x, t: fir_pallas(x, t, interpret=False),
                   _shape((8, 2048), one_chip), _shape((11,), one_chip))


def test_fft_kernel_compiles(one_chip):
    _assert_mosaic(lambda re, im: fft_pallas(re, im, interpret=False),
                   _shape((32, 512), one_chip), _shape((32, 512), one_chip))


@pytest.mark.parametrize("layout", ["interleaved", "half"])
def test_rope_kernel_compiles(layout, one_chip):
    _assert_mosaic(lambda x, p: rope_pallas(x, p, layout=layout,
                                            interpret=False),
                   _shape((256, 64), one_chip),
                   _shape((256,), one_chip, jnp.int32))
