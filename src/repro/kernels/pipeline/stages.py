"""Stage registry for the fused pipeline graph compiler.

The paper's core claim is flexibility — ONE substrate (columns of RCs
fed from very-wide registers) accelerating MANY kernels. The code-level
analogue is this registry: a *stage* is one fused-kernel building block
(FIR, delineation, windowing, packed rFFT, a matmul epilogue, ...) with
a declared VMEM operand signature, and a *stage graph*
(`graph.py:StageGraph`) chains registered stages into ONE `pallas_call`
body — single VMEM residency, in-kernel framing and `outputs=` elision
all shared across workloads. The biosignal app
(`kernel.py`) and the streaming ASR front-end (`asr.py`) are two graphs
over this one registry; `docs/STAGE_GRAPHS.md` is the authoring guide.

A stage declares four things:

* ``kind`` — ``"fir"`` for the mandatory FIRST stage (a causal k-tap
  FIR; the stream framing machinery keys its frame-local head
  patch off this stage's tap count), ``"map"`` for everything else;
* ``operands`` — the names of the staged VMEM table operands its body
  reads (FIR taps, twiddles, Hann window, mel weights, the odd-even
  sort masks — the paper keeps such tables in the SPM). A graph binds
  each name to a concrete array once, outside the kernel;
* ``requires`` / ``produces`` — the state keys (per-frame tensors that
  NEVER leave VMEM) the body consumes and defines. The graph compiler
  checks the dataflow at build time and uses it for output elision:
  a stage only runs when a *requested* output transitively depends on
  it (`graph.py:stages_to_run`);
* ``body`` — ``body(state, tables, params) -> dict`` of new state
  entries, pure jnp on VMEM-resident values.

Error taxonomy (all rooted at `StageGraphError`, a `ValueError` so
legacy ``except ValueError`` call sites still catch):
`UnknownStageError` (a graph names a stage that was never registered),
`OperandMismatchError` (a stage's operand signature is not satisfied by
the graph's operand list, or the dataflow is unsatisfiable), and
`UnknownGraphError` (`graph.py:get_graph_factory` lookup miss).
`tests/test_stage_graph.py` pins all three.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

__all__ = ["Stage", "StageGraphError", "UnknownStageError",
           "OperandMismatchError", "UnknownGraphError", "register_stage",
           "get_stage", "registered_stages"]


class StageGraphError(ValueError):
    """Root of the stage-graph error taxonomy (a `ValueError`: graph
    construction errors are bad-argument errors to the caller)."""


class UnknownStageError(StageGraphError):
    """A graph referenced a stage name that is not in the registry."""


class OperandMismatchError(StageGraphError):
    """A stage's declared operand signature (or state dataflow) is not
    satisfied by the graph binding it."""


class UnknownGraphError(StageGraphError):
    """`get_graph_factory` was asked for a graph name never registered."""


@dataclasses.dataclass(frozen=True)
class Stage:
    """One fused-kernel building block (see the module docstring).

    Frozen + hashable so a `StageGraph` holding stages can be a STATIC
    jit argument of the graph entries (`graph.py:graph_stream_pallas`);
    the ``body`` callable hashes by identity, which is stable for the
    module-level registrations this registry holds.
    """
    name: str
    kind: str                       # "fir" | "map"
    operands: tuple                 # staged VMEM table names the body reads
    requires: tuple                 # state keys consumed
    produces: tuple                 # state keys defined
    body: Callable                  # body(state, tables, params) -> dict

    def __post_init__(self):
        if self.kind not in ("fir", "map"):
            raise StageGraphError(
                f"stage {self.name!r}: kind must be 'fir' or 'map', "
                f"got {self.kind!r}")
        if self.kind == "fir" and len(self.operands) != 1:
            raise OperandMismatchError(
                f"fir stage {self.name!r} must declare exactly one "
                f"operand (its tap table), got {self.operands}")


_REGISTRY: dict[str, Stage] = {}


def register_stage(name: str, *, kind: str = "map", operands=(),
                   requires=(), produces=()):
    """Decorator registering ``fn`` as the body of stage ``name``.

    >>> @register_stage("hann", operands=("hann",),
    ...                 requires=("filtered",), produces=("windowed",))
    ... def _hann(state, tables, params): ...

    Re-registering an existing name raises `StageGraphError` — stages
    are process-wide singletons shared by every graph that names them
    (the biosignal and ASR graphs share ``"fir"``).
    """
    def deco(fn):
        if name in _REGISTRY:
            raise StageGraphError(f"stage {name!r} is already registered")
        _REGISTRY[name] = Stage(name=name, kind=kind,
                                operands=tuple(operands),
                                requires=tuple(requires),
                                produces=tuple(produces), body=fn)
        return fn
    return deco


def get_stage(name: str) -> Stage:
    """Registry lookup; raises the typed `UnknownStageError` on a miss."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise UnknownStageError(
            f"unknown stage {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def registered_stages() -> tuple:
    """Registered stage names, sorted (docs/tests introspection)."""
    return tuple(sorted(_REGISTRY))
