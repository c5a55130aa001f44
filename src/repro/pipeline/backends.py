"""Pluggable execution backends for compiled CUTIE programs.

A backend maps each compiled :class:`repro.core.engine.LayerInstr` onto an
executable representation once at pipeline-construction time (``lower``) and
then runs it inside the jitted program (``apply``).  All backends share one
layer epilogue (merged pooling on pre-threshold integers + the folded
two-threshold compare + the degenerate-channel fixup), so their trit
outputs are bit-identical — the same compiled program runs on any of them,
like the ASIC's layer FIFO driving different micro-architectural
implementations of the OCU array.

Backends:

* ``ref``    — ``lax.conv_general_dilated`` int32 oracle (fast on CPU),
* ``pallas`` — the weight-stationary Pallas OCU-array kernel
  (`repro.kernels.ternary_conv2d`); interpret mode when JAX runs on the
  CPU (``JAX_PLATFORMS=cpu``), compiled for the chip on a TPU.  The whole
  layer epilogue (pooling, thresholds, constant channels) runs inside the
  kernel, so the int32 accumulator never leaves VMEM — pool layers
  included,
* ``packed`` — weights stored packed at 5 trits/byte
  (`repro.kernels.trit_codec` layout, paper §III-A) and decoded *inside*
  the conv kernel next to the taps that consume them; the deployment/HBM-
  compression path,
* ``fused``  — trunk-fused execution: maximal runs of uniform layers
  (`repro.compiler.trunks.plan_segments`) execute inside ONE Pallas
  megakernel (`repro.kernels.fused_trunk`) with all weights stationary in
  VMEM and activations ping-ponging between two VMEM scratch buffers, so
  zero inter-layer HBM traffic occurs inside a trunk; the residual
  inter-trunk activations travel trit-packed at 5/byte.  Non-fusible
  layers fall back to the per-layer kernel; traced runs (Tracer hooks
  need every intermediate activation) execute per-layer too, so stats
  stay identical across backends.

Selection: by name via :func:`get_backend`, or auto-detected (``pallas`` on
TPU, else ``ref``); the ``REPRO_PIPELINE_BACKEND`` env var overrides.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import codec, engine, folding

Array = jax.Array


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    """Probe the default jax platform once; device topology is static.

    A JAX backend that fails to initialise raises here: a broken chip
    must not turn silently into interpret mode on the CPU.  CPU runs get
    interpret mode by selecting the CPU (``JAX_PLATFORMS=cpu``)."""
    return jax.devices()[0].platform == "tpu"


def _finish_layer(z: Array, instr: engine.LayerInstr) -> Array:
    """Shared epilogue: merged pooling (pre-threshold) + folded compares."""
    if instr.pool is not None:
        z = engine._pool_pre_threshold(z, instr.thresholds, instr.pool)
    return folding.apply_thresholds(z, instr.thresholds)


class Backend:
    """Protocol: lower a LayerInstr once, apply it inside the jitted run.

    ``lower`` returns an arrays-only pytree (so uniform programs can be
    stacked and scanned); static metadata stays on the LayerInstr, which
    ``apply`` receives alongside.  ``apply`` must be traceable and must
    produce trit outputs bit-identical to the ``ref`` backend.

    Backends may additionally implement ``build_program(program,
    in_shape, emit_stats=False)`` returning a traceable ``fn(lowered, x)
    -> (out, recs)`` that executes the *whole* program; the pipeline
    prefers it for untraced runs, and — with ``emit_stats=True``, where
    ``recs`` becomes the (L, 3) int32 in-kernel counter block — for
    tracers that declare ``kernel_stats`` (per-layer fallback is then
    reserved for tracers that genuinely need every boundary).

    ``apply_with_stats`` is the per-layer counterpart: one layer plus its
    (3,) int32 counters (in-zero, out-zero, window-toggle — the
    `repro.pipeline.tracer.layer_stat_counts` layout).  The base
    implementation derives the counts from the activations with the jnp
    oracle; kernel backends override it to emit them from inside the
    ``pallas_call``.
    """

    name: str = "?"

    def lower(self, instr: engine.LayerInstr) -> Any:
        raise NotImplementedError

    def apply(self, lowered: Any, x: Array, instr: engine.LayerInstr) -> Array:
        raise NotImplementedError

    def apply_with_stats(self, lowered: Any, x: Array,
                         instr: engine.LayerInstr):
        """(y, (3,) int32 counters) for one layer; oracle fallback."""
        from repro.pipeline.tracer import layer_stat_counts

        y = self.apply(lowered, x, instr)
        return y, layer_stat_counts(x, y, instr)


@dataclasses.dataclass(frozen=True)
class RefBackend(Backend):
    """Pure-jnp oracle: integer conv via ``lax.conv_general_dilated``."""

    name: str = dataclasses.field(default="ref", init=False)

    def lower(self, instr):
        return {"w": instr.weights, "th": instr.thresholds}

    def apply(self, lowered, x, instr):
        z = engine.conv2d_int(x, lowered["w"], instr.stride, instr.padding)
        return _finish_layer(z, instr._replace_thresholds(lowered["th"]))


@dataclasses.dataclass(frozen=True)
class PallasBackend(Backend):
    """Weight-stationary Pallas OCU-array conv, fully fused epilogue."""

    interpret: bool = dataclasses.field(default_factory=lambda: not _on_tpu())
    name: str = dataclasses.field(default="pallas", init=False)

    def lower(self, instr):
        return {"w": instr.weights, "th": instr.thresholds}

    def apply(self, lowered, x, instr, emit_stats: bool = False):
        from repro.kernels import ternary_conv2d as K

        th: folding.ChannelThresholds = lowered["th"]
        return K.ternary_conv2d_pallas(
            x, lowered["w"], stride=instr.stride, padding=instr.padding,
            t_lo=th.t_lo, t_hi=th.t_hi, flip=th.flip,
            const=th.const, is_const=th.is_const, pool=instr.pool,
            emit_stats=emit_stats, interpret=self.interpret)

    def apply_with_stats(self, lowered, x, instr):
        return self.apply(lowered, x, instr, emit_stats=True)


@dataclasses.dataclass(frozen=True)
class PackedBackend(Backend):
    """Weights live packed (5 trits/byte); the conv kernel decodes them."""

    interpret: bool = dataclasses.field(default_factory=lambda: not _on_tpu())
    name: str = dataclasses.field(default="packed", init=False)

    def lower(self, instr):
        return {"wp": codec.pack_filter_rows(instr.weights),
                "th": instr.thresholds}

    def apply(self, lowered, x, instr, emit_stats: bool = False):
        from repro.kernels import ternary_conv2d as K

        th: folding.ChannelThresholds = lowered["th"]
        k, _, cin, _ = instr.weights.shape
        return K.ternary_conv2d_packed_pallas(
            x, lowered["wp"], k=k, cin=cin, stride=instr.stride,
            padding=instr.padding, t_lo=th.t_lo, t_hi=th.t_hi, flip=th.flip,
            const=th.const, is_const=th.is_const, pool=instr.pool,
            emit_stats=emit_stats, interpret=self.interpret)

    def apply_with_stats(self, lowered, x, instr):
        return self.apply(lowered, x, instr, emit_stats=True)


@dataclasses.dataclass(frozen=True)
class FusedBackend(PallasBackend):
    """Trunk-fused execution: one megakernel per run of uniform layers.

    ``vmem_budget`` (bytes) bounds each trunk's on-chip residency
    (default `repro.compiler.trunks.DEFAULT_VMEM_BUDGET`);
    ``pack_boundaries`` makes consecutive fused trunks exchange their
    activations as 5-trits/byte packed bytes — the producer packs in
    its epilogue, the consumer decodes in its prologue, so the tensor
    crossing HBM between them is 5x smaller than int8 trits (boundaries
    that touch a per-layer segment stay dense).  Per-layer execution
    (such segments, traced runs, meshed pipelines) inherits the fully
    fused PallasBackend kernel, so both paths share one epilogue
    implementation.
    """

    vmem_budget: int | None = None
    pack_boundaries: bool = True
    name: str = dataclasses.field(default="fused", init=False)

    def plan(self, program: engine.CutieProgram, in_shape):
        from repro.compiler import trunks

        return trunks.plan_segments(program, in_shape, self.vmem_budget)

    def build_program(self, program: engine.CutieProgram, in_shape,
                      emit_stats: bool = False):
        """Whole-program trunk-fused execution.

        With ``emit_stats=True`` every segment also emits the per-layer
        (3,) int32 switching counters — the fused trunks from inside
        their megakernel, per-layer segments from the per-layer kernel —
        and ``fn`` returns ``(out, counts)`` with ``counts`` the
        program's (L, 3) block in layer order, ready for a
        ``kernel_stats`` tracer's ``finalize_counts``.
        """
        from repro.compiler import trunks
        from repro.kernels import fused_trunk as FT

        segments = self.plan(program, in_shape)
        layers = program.layers
        metas = {seg: tuple((layers[i].stride, layers[i].pool)
                            for i in range(seg.start, seg.stop))
                 for seg in segments if seg.fused}
        # Per-trunk common input width: the head's Cin and the trunk
        # width C zero-padded to max(Cin, C) — exact, zero weights only
        # ever meet zero activations.
        cus = {seg: trunks.trunk_cin(layers[seg.start:seg.stop])
               for seg in segments if seg.fused}
        # fused->fused boundaries exchange packed bytes (kernel-side
        # pack/unpack); each consumer needs its logical input shape.
        hw = trunks.segment_shapes(layers, in_shape[1:3])
        packed_after = [self.pack_boundaries and a.fused and b.fused
                        for a, b in zip(segments, segments[1:])] + [False]

        def pad_ch(a, cu, axis):
            n = cu - a.shape[axis]
            if n == 0:
                return a
            pads = [(0, 0)] * a.ndim
            pads[axis] = (0, n)
            return jnp.pad(a, pads)

        def fn(lowered, x):
            cur = x
            counts = []                 # per-layer (3,) int32, in order
            for si, seg in enumerate(segments):
                if seg.fused:
                    rng = range(seg.start, seg.stop)
                    cu = cus[seg]
                    ws = jnp.stack([pad_ch(lowered[i]["w"], cu, 2)
                                    for i in rng])
                    th = [jnp.stack([getattr(lowered[i]["th"], f)
                                     for i in rng])
                          for f in ("t_lo", "t_hi", "flip", "const",
                                    "is_const")]
                    if si > 0 and packed_after[si - 1]:
                        h, w = hw[seg.start]
                        packed_in = (in_shape[0], h, w,
                                     layers[seg.start].weights.shape[2])
                    else:
                        packed_in = None
                        cur = pad_ch(cur, cu, 3)
                    cur = FT.fused_trunk_pallas(
                        cur, ws, *th, metas=metas[seg],
                        packed_in=packed_in, pack_out=packed_after[si],
                        emit_stats=emit_stats,
                        stats_cin=layers[seg.start].weights.shape[2],
                        interpret=self.interpret)
                    if emit_stats:
                        cur, seg_counts = cur
                        counts.extend(seg_counts[i] for i in range(len(seg)))
                else:
                    for i in range(seg.start, seg.stop):
                        if emit_stats:
                            cur, row = self.apply_with_stats(
                                lowered[i], cur, layers[i])
                            counts.append(row)
                        else:
                            cur = self.apply(lowered[i], cur, layers[i])
            if emit_stats:
                return cur, jnp.stack(counts)
            return cur, []

        return fn


_REGISTRY = {
    "ref": RefBackend,
    "pallas": PallasBackend,
    "packed": PackedBackend,
    "fused": FusedBackend,
}


def available_backends() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def default_backend_name() -> str:
    env = os.environ.get("REPRO_PIPELINE_BACKEND")
    if env:
        return env
    return "pallas" if _on_tpu() else "ref"


def get_backend(backend: str | Backend | None = None, **kwargs) -> Backend:
    """Resolve a backend by name / instance / auto-detection."""
    if isinstance(backend, Backend):
        return backend
    name = backend or default_backend_name()
    if name == "pallas_interpret":          # kernels/ops.py spelling
        name, kwargs = "pallas", dict(kwargs, interpret=True)
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown backend {name!r}; available: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)
