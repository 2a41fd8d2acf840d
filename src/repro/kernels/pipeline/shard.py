"""Multi-column sharding for the fused biosignal pipeline.

VWR2A scales throughput by replicating columns: the CGRA deals passes
round-robin across identical column slices that share the scratchpad
crossbar, and archsim's `VWR2A(n_columns=...)` models exactly that
(conserved activity, ~1/D cycles). This module is the Pallas-path
analogue: a `data`-axis `shard_map` around `pipeline_pallas` /
`pipeline_stream_pallas` that deals frame-blocks across devices the way
the simulator deals passes across columns.

The raw-signal split happens on HOP boundaries: column d owns the
contiguous run of frames [d*n_d, (d+1)*n_d) (n_d = ceil(n_frames / D) —
the same conserved-work deal as archsim's round-robin, collapsed to one
run per column so the inter-column halo stays minimal), and its chunk is

    signal[d*n_d*hop : d*n_d*hop + n_d*hop + (window - hop)]

i.e. each column stages ~n_samples/D body samples plus ONE `window-hop`
overlap halo replicated from its right neighbour — the inter-device
mirror of the in-kernel overlap sharing (PR 3), which keeps per-device
HBM traffic at ~n_samples/D instead of n_frames*window/D.

Every column runs the SAME single-device kernel on its chunk, so sharded
outputs are bit-identical to the unsharded call (each frame's pipeline
reads only its own window: the chunk FIR's frame-local transient patch
makes frames independent of how chunks are cut). When no mesh is
available (or D exceeds the device count) the identical per-column body
runs serially on one device — the fallback tests rely on for
device-count-independent equivalence properties.

LOAD-AWARE DEAL: ``weights`` generalizes the equal split to non-uniform
hop-aligned shares — column d owns a contiguous run of frames whose count
is proportional to its weight (largest-remainder apportionment, so shares
sum to exactly n_frames and every chunk still starts on a hop boundary;
the halo logic is unchanged). ``weights=None`` is the equal-deal fast
path, bit-for-bit the PR-4 behaviour. The serving layer feeds measured
per-column throughput (`serve.stream.StreamTelemetry` EWMAs) in as the
weight vector so an externally loaded column — e.g. one shared with the
LM engine — is dealt a proportionally smaller share: the software
analogue of work-stealing between VWR2A columns.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.pipeline.kernel import (OUTPUTS, canonical_outputs,
                                           empty_outputs, pipeline_pallas,
                                           pipeline_stream_pallas,
                                           stream_frame_count)

__all__ = ["Deal", "column_frames", "column_shares", "column_chunks",
           "requeue_ranges", "pipeline_sharded", "pipeline_stream_sharded",
           "data_mesh_size"]


def data_mesh_size(mesh) -> int:
    """Size of the mesh's `data` axis (the column-replication axis)."""
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)


def _check_mesh(mesh, n_columns: int) -> None:
    """`mesh=None` means the serial fallback by design, but a PROVIDED
    mesh whose data axis doesn't match n_columns is a misconfiguration —
    silently running serial would hand back single-device throughput with
    zero diagnostics."""
    assert mesh is None or data_mesh_size(mesh) == n_columns, (
        f"mesh data axis {data_mesh_size(mesh)} != n_columns {n_columns}; "
        f"build the mesh with make_local_mesh(data=n_columns) or pass "
        f"mesh=None for the serial fallback")


def column_frames(n_frames: int, n_columns: int) -> int:
    """Frames per column: the conserved-work equal deal. Every column
    processes the same padded count (shard_map shards must agree on
    shape); the `n_columns*column_frames - n_frames` pad frames are
    trimmed after."""
    assert n_columns >= 1, n_columns
    return -(-max(n_frames, 1) // n_columns)


def column_shares(n_frames: int, n_columns: int,
                  weights=None) -> tuple[int, ...]:
    """Per-column frame counts for the deal.

    ``weights=None``: the equal deal — every column the same padded
    `column_frames` count (sum may exceed n_frames; the pad is trimmed).
    With ``weights`` (n_columns non-negative finites, sum > 0): column d's
    share is proportional to weights[d], quantized by largest-remainder
    apportionment so the shares sum to EXACTLY n_frames — contiguous
    frame runs cover every frame once with no overlap, and since frames
    start on hop multiples every chunk boundary stays hop-aligned. A
    zero-weight (cold/reserved) column gets zero frames.
    """
    assert n_columns >= 1, n_columns
    if weights is None:
        return (column_frames(n_frames, n_columns),) * n_columns
    w = [float(x) for x in weights]
    assert len(w) == n_columns, (len(w), n_columns)
    assert all(x >= 0.0 and x == x and x != float("inf") for x in w), w
    total = sum(w)
    assert total > 0.0, "weights must not all be zero"
    ideal = [n_frames * x / total for x in w]
    base = [int(i) for i in ideal]
    # hand the leftover frames to the largest fractional remainders
    # (ties -> lower column index, so the deal is deterministic)
    order = sorted(range(n_columns), key=lambda d: (base[d] - ideal[d], d))
    for d in order[: n_frames - sum(base)]:
        base[d] += 1
    assert sum(base) == n_frames, (base, n_frames)
    return tuple(base)


def requeue_ranges(ranges, n_columns: int,
                   weights=None) -> list[list[tuple[int, int]]]:
    """Deal a dead column's unretired frame ranges across columns.

    ``ranges`` is an ordered list of ``(start, count)`` frame runs (frame
    indices, so every boundary is hop-aligned by construction — frame i
    starts at sample ``i*hop``). The total frame count is apportioned by
    the SAME largest-remainder arithmetic as the initial deal
    (`column_shares`, so a zero-weight — dead — column receives nothing),
    then the runs are walked in order and split at share boundaries:
    column d's portion is a list of ``(start, count)`` runs covering
    exactly its share.

    Properties the chaos tests pin: concatenating every column's runs in
    column order reproduces the input frame set exactly (full coverage,
    no overlap, order preserved), every run is non-empty, and per-column
    counts equal `column_shares` of the total. Contiguous runs landing on
    the same column COALESCE into one (the input runs are dispatch-sized
    fragments of one contiguous share; re-fragmenting them across a
    share boundary would make a survivor pay two dispatch overheads for
    adjacent frames). This is the requeue step of the fault-tolerant
    serving loop (`serve/fault.py`): the degraded deal is just the
    healthy deal with dead columns' weights zeroed.
    """
    ranges = [(int(s), int(c)) for s, c in ranges if c > 0]
    total = sum(c for _, c in ranges)
    if total == 0:
        return [[] for _ in range(n_columns)]
    # weights=None means the equal deal; column_shares' None path pads to
    # a uniform per-column count (shard_map shape agreement), but requeue
    # needs shares summing to EXACTLY the frame total — use explicit
    # equal weights to get the largest-remainder exact-sum path
    shares = column_shares(total, n_columns,
                           weights if weights is not None
                           else (1.0,) * n_columns)
    out: list[list[tuple[int, int]]] = [[] for _ in range(n_columns)]
    it = iter(ranges)
    cur_start, cur_count = 0, 0
    for d, share in enumerate(shares):
        need = share
        while need > 0:
            if cur_count == 0:
                cur_start, cur_count = next(it)
            take = min(need, cur_count)
            if out[d] and out[d][-1][0] + out[d][-1][1] == cur_start:
                out[d][-1] = (out[d][-1][0], out[d][-1][1] + take)
            else:
                out[d].append((cur_start, take))
            cur_start += take
            cur_count -= take
            need -= take
    return out


@dataclasses.dataclass(frozen=True)
class Deal:
    """The result of one column deal (`column_chunks`), named.

    ``chunks`` is the `(D, L)` staged-signal array (None when the signal
    frames to nothing), ``n_frames`` the global frame count, ``shares``
    the per-column frame counts (`column_shares`). Iterates like the
    legacy ``(chunks, n_frames, shares)`` 3-tuple, so both
    ``deal.shares`` and ``chunks, n, shares = column_chunks(...)``
    read correctly at call sites."""
    chunks: object
    n_frames: int
    shares: tuple[int, ...]

    def __iter__(self):
        return iter((self.chunks, self.n_frames, self.shares))


def column_chunks(signal, window: int, hop: int, n_columns: int,
                  weights=None) -> Deal:
    """Split a raw 1-D signal into per-column chunks on hop boundaries.

    Returns a `Deal`. ``Deal.chunks`` is `(D, L)` with
    `L = max(shares)*hop + window - hop`: row d starts at the first
    sample of its first owned frame (`offset_d*hop`, hop-aligned by
    construction) and carries its `window-hop` right-halo (replicated
    from the neighbour's first samples), zero-padded past the signal end
    — so row d's first ``shares[d]`` framed windows are exactly the ones
    frame-global indices [offset_d, offset_d + shares[d]) would produce.

    With the equal deal (``weights=None``) every share is the same padded
    `column_frames` count and rows frame to exactly that count — the PR-4
    behaviour. With ``weights`` the shares are the non-uniform
    `column_shares` deal (summing to n_frames exactly); rows are padded
    to the widest share's length so shard_map shards agree on shape, and
    a row's frames past its own share are discard-on-trim duplicates of
    its neighbour's frames. `n_frames == 0` yields
    ``Deal(None, 0, (0,)*D)``.
    """
    sig = jnp.asarray(signal)
    assert sig.ndim == 1, sig.shape
    n = stream_frame_count(sig.shape[0], window, hop)
    if n == 0:
        return Deal(None, 0, (0,) * n_columns)
    shares = column_shares(n, n_columns, weights)
    L = max(shares) * hop + (window - hop)
    offsets = [sum(shares[:d]) for d in range(n_columns)]
    total = max(off * hop + L for off in offsets)
    if total > sig.shape[0]:
        sig = jnp.concatenate(
            [sig, jnp.zeros((total - sig.shape[0],), sig.dtype)])
    chunks = jnp.stack([sig[off * hop: off * hop + L] for off in offsets])
    return Deal(chunks, n, shares)


def _trim(out: dict, n: int) -> dict:
    return {k: v[:n] for k, v in out.items()}


def _stream_body(chunk, taps, w, b, *, window, hop, fft_size, interpret,
                 block_frames, outputs):
    """One column's work: the unsharded single-device kernel on a (1, L)
    chunk row. Shared verbatim by the shard_map shard and the serial
    fallback, which is what makes the two paths bit-identical."""
    return pipeline_stream_pallas(
        chunk[0], taps, w, b, window=window, hop=hop, fft_size=fft_size,
        interpret=interpret, block_frames=block_frames, outputs=outputs)


@functools.lru_cache(maxsize=64)
def _stream_shard_fn(mesh, window, hop, fft_size, interpret, block_frames,
                     outputs):
    """Memoized jit(shard_map(...)) per (mesh, static config): an eager
    shard_map re-traces every dispatch, which would swamp the per-batch
    runtime; Mesh hashes by value, so every stream with the same column
    layout shares one compiled executable."""
    body = functools.partial(_stream_body, window=window, hop=hop,
                             fft_size=fft_size, interpret=interpret,
                             block_frames=block_frames, outputs=outputs)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P(), P(), P()),
        out_specs=P("data"),
        check_vma=False))         # pallas_call has no replication rule


def pipeline_stream_sharded(signal, taps, w, b, *, window: int, hop: int,
                            n_columns: int, mesh=None, fft_size: int = 512,
                            interpret: bool = True,
                            block_frames: int | None = None,
                            outputs: tuple = OUTPUTS, weights=None):
    """`pipeline_stream_pallas` dealt across `n_columns` column replicas.

    With `mesh` (a mesh whose `data` axis has >= n_columns devices... in
    fact exactly n_columns — build it with
    `launch.mesh.make_local_mesh(data=n_columns)`), the per-column chunks
    are `shard_map`ped over the `data` axis: each device stages only its
    ~n_samples/D chunk + halo and runs the fused kernel on it. Without a
    mesh the same per-column body runs serially — identical outputs, so
    every equivalence property is testable on a single device.

    ``weights`` switches the equal deal to the non-uniform
    `column_shares` deal (load-aware: a slow column gets a small share).
    On the serial fallback each column runs EXACTLY its own share — the
    per-column wall times really are proportional to the deal, which is
    what the `table5/stream_hetero` bench measures. Under shard_map the
    shards stay shape-uniform (padded to the widest share; the pad frames
    are discarded on trim), so a smaller share still cuts the loaded
    column's staged bytes and valid output rows. Outputs are bit-identical
    to the single-device kernel for ANY valid weight vector.

    Invariants: every chunk boundary is HOP-ALIGNED (frames start on hop
    multiples, so the deal never splits a frame) and the chunk FIR's
    frame-local transient patch makes each frame independent of where
    the signal was cut — the two facts that make the deal numerically
    invisible. See `docs/ARCHITECTURE.md` (column replication) for the
    paper mapping and `docs/BENCHMARKS.md` for the `--check-columns` /
    `--check-hetero` gates this entry backs.
    """
    outputs = canonical_outputs(outputs)
    _check_mesh(mesh, n_columns)
    F, C = w.shape
    deal = column_chunks(signal, window, hop, n_columns, weights)
    chunks, n, shares = deal.chunks, deal.n_frames, deal.shares
    if n == 0:
        return empty_outputs(window, F, C, jnp.asarray(signal).dtype,
                             outputs)
    body = functools.partial(_stream_body, window=window, hop=hop,
                             fft_size=fft_size, interpret=interpret,
                             block_frames=block_frames, outputs=outputs)
    if n_columns == 1:
        return _trim(body(chunks, taps, w, b), n)
    if mesh is not None:
        sharded = _stream_shard_fn(mesh, window, hop, fft_size, interpret,
                                   block_frames, outputs)
        out = sharded(chunks, taps, w, b)
        if weights is None:
            return _trim(out, n)
        # non-uniform deal: every shard framed max(shares) rows; keep each
        # column's own share and drop its pad rows
        n_max = max(shares)
        keep = [slice(d * n_max, d * n_max + s)
                for d, s in enumerate(shares) if s]
        return {k: jnp.concatenate([v[sl] for sl in keep])
                for k, v in out.items()}
    # serial-column fallback: same deal, one device. Non-uniform shares
    # run each column on exactly its own share's samples (chunk rows are
    # padded to the widest share; the slice undoes the pad) so serial
    # per-column timing reflects the deal.
    if weights is None:
        outs = [body(chunks[d: d + 1], taps, w, b)
                for d in range(n_columns)]
    else:
        outs = [body(chunks[d: d + 1, : s * hop + (window - hop)],
                     taps, w, b)
                for d, s in enumerate(shares) if s]
    return _trim({k: jnp.concatenate([o[k] for o in outs]) for k in outs[0]},
                 n)


def _framed_body(rows, taps, w, b, *, fft_size, interpret, block_rows,
                 outputs):
    return pipeline_pallas(rows, taps, w, b, fft_size=fft_size,
                           interpret=interpret, block_rows=block_rows,
                           outputs=outputs)


@functools.lru_cache(maxsize=64)
def _framed_shard_fn(mesh, fft_size, interpret, block_rows, outputs):
    body = functools.partial(_framed_body, fft_size=fft_size,
                             interpret=interpret, block_rows=block_rows,
                             outputs=outputs)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P(), P(), P()),
        out_specs=P("data"),
        check_vma=False))         # pallas_call has no replication rule


def pipeline_sharded(frames, taps, w, b, *, n_columns: int, mesh=None,
                     fft_size: int = 512, interpret: bool = True,
                     block_rows: int | None = None,
                     outputs: tuple = OUTPUTS):
    """`pipeline_pallas` on pre-framed (R, S) windows, rows dealt across
    columns: row-block d of ceil(R/D) windows goes to column d (pad rows
    are trimmed after). The framed counterpart of
    `pipeline_stream_sharded` — no halo needed, frames carry their own
    overlap."""
    outputs = canonical_outputs(outputs)
    _check_mesh(mesh, n_columns)
    R, S = frames.shape
    F, C = w.shape
    if R == 0:
        return empty_outputs(S, F, C, frames.dtype, outputs)
    body = functools.partial(_framed_body, fft_size=fft_size,
                             interpret=interpret, block_rows=block_rows,
                             outputs=outputs)
    if n_columns == 1:
        return body(frames, taps, w, b)
    r_d = column_frames(R, n_columns)
    if n_columns * r_d > R:
        frames = jnp.concatenate(
            [frames, jnp.zeros((n_columns * r_d - R, S), frames.dtype)])
    if mesh is not None:
        sharded = _framed_shard_fn(mesh, fft_size, interpret, block_rows,
                                   outputs)
        return _trim(sharded(frames, taps, w, b), R)
    outs = [body(frames[d * r_d: (d + 1) * r_d], taps, w, b)
            for d in range(n_columns)]
    return _trim({k: jnp.concatenate([o[k] for o in outs]) for k in outs[0]},
                 R)
