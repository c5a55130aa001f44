"""Trunk segmentation: carve a CutieProgram into maximal fusible runs.

The fused execution backend (`repro.pipeline.backends.FusedBackend`)
runs a *trunk* — a contiguous run of uniform layers — inside one Pallas
megakernel (`repro.kernels.fused_trunk`), with all weights stationary in
VMEM and activations ping-ponging between two VMEM scratch buffers.
This pass decides where the trunks are:

* a trunk is headed by any fully-padded layer; its output width C
  becomes the trunk width.  The head's Cin may differ from C (the
  CUTIE-CNN case: a thermometer-fed 126-channel first layer in front of
  a 128-wide trunk) — the backend zero-pads input channels to the
  common width, which is exact because zero weights meet zero
  activations,
* consecutive layers join the trunk while they are fully padded, share
  the trunk's kernel size and have Cin == Cout == C (the ping-pong
  buffers are sized once per trunk; stride and merged pooling are fine —
  they only shrink the static spatial dims) **and** the trunk still
  fits the VMEM budget (weights + stacked thresholds + the two
  activation buffers + the kernel's input/output blocks, priced by
  :func:`trunk_vmem_bytes`),
* everything else (width changes mid-run, unpadded layers, budget
  overflow) breaks the trunk; single-layer remainders are left to the
  per-layer kernels, which are exactly equivalent there.

The budget defaults to 12 MiB — a TPU core's ~16 MiB VMEM minus
headroom for the Mosaic pipeline's own double buffering.  Segmentation
depends on the input shape (the activation buffers scale with batch and
spatial dims), so the pipeline plans per jit specialization.
"""

from __future__ import annotations

import dataclasses

from repro.core import engine

#: Default VMEM budget in bytes: ~16 MiB/core minus pipelining headroom.
DEFAULT_VMEM_BUDGET = 12 * 2 ** 20

#: Stacked per-channel threshold bytes: t_lo/t_hi float32 + flip/const/
#: is_const int8.
_THRESHOLD_BYTES_PER_CHANNEL = 4 + 4 + 1 + 1 + 1


@dataclasses.dataclass(frozen=True)
class Trunk:
    """One execution segment: program layers [start, stop).

    ``fused`` segments run inside a single fused-trunk megakernel;
    non-fused segments fall back to the per-layer kernels.
    ``vmem_bytes`` is the fused segment's priced VMEM residency (0 for
    per-layer segments).  ``reason`` says *why* the segment has its
    shape — why a per-layer segment could not fuse
    (``"unpadded"`` / ``"width-change"`` / ``"vmem-budget"`` /
    ``"short-run"``), or why a fused trunk stopped growing
    (``"vmem-budget"``; empty when it simply reached a natural
    boundary) — so degradations surface in ``execution_plan()`` instead
    of silently happening.
    """

    start: int
    stop: int
    fused: bool
    vmem_bytes: int = 0
    reason: str = ""

    def __len__(self) -> int:
        return self.stop - self.start


def segment_shapes(layers, in_hw) -> list[tuple[int, int]]:
    """Activation dims [input, after layer 0, ...] for a layer run."""
    h, w = in_hw
    shapes = [(h, w)]
    for instr in layers:
        h, w = engine.layer_out_dims(instr.kernel_size, instr.stride,
                                     instr.padding, instr.pool, h, w)
        shapes.append((h, w))
    return shapes


def trunk_cin(layers) -> int:
    """The trunk's common (zero-padded) input channel width."""
    return max(layers[0].weights.shape[2], layers[0].weights.shape[3])


def trunk_vmem_bytes(layers, in_shape) -> int:
    """VMEM residency of a fused trunk fed an (N, H, W, Cin) input.

    Everything the megakernel keeps on-chip at once: the stationary
    weight stack (head Cin zero-padded to the trunk width), the stacked
    per-channel thresholds, the two padded ping-pong activation buffers
    (sized by the trunk's *first* layer — dims only shrink), the
    kernel's input/output blocks, and — the dominant transient — the
    float32 im2col patch (N*H*W x K*K*Cin, gathered at stride 1 over the
    layer's input dims) plus accumulator that each layer's
    completely-unrolled window dot materializes (its largest layer bounds
    the peak; only one layer's patch is live at a time).
    """
    n, h, w, _ = in_shape
    k = layers[0].kernel_size
    p = k // 2
    cin = trunk_cin(layers)
    cout = layers[0].weights.shape[-1]
    weights = len(layers) * k * k * cin * cout
    thresholds = len(layers) * cout * _THRESHOLD_BYTES_PER_CHANNEL
    scratch = 2 * n * (h + 2 * p) * (w + 2 * p) * cin
    shapes = segment_shapes(layers, (h, w))
    transient = 0
    for hi, wi in shapes[:-1]:      # windows gathered at stride 1
        transient = max(transient, n * hi * wi * (k * k * cin + cout) * 4)
    oh, ow = shapes[-1]
    io = n * h * w * cin + n * oh * ow * cout
    return weights + thresholds + scratch + transient + io


def _trunk_stop(layers, i: int, in_shape, budget: int) -> tuple[int, str]:
    """Longest fusible trunk starting at layer i (may be length 1).

    Returns ``(stop, reason)`` — the exclusive stop index and why the
    trunk stopped growing there: ``"unpadded"`` (the head or the next
    layer lacks full padding), ``"width-change"`` (kernel size or
    channel width breaks uniformity), ``"vmem-budget"`` (the next layer
    would overflow the budget) or ``"end"`` (ran off the program).
    """
    head = layers[i]
    if not head.padding:
        return i + 1, "unpadded"
    k0 = head.kernel_size
    c0 = head.weights.shape[-1]
    j = i + 1
    while j < len(layers):
        instr = layers[j]
        if not instr.padding:
            return j, "unpadded"
        if (instr.kernel_size != k0
                or instr.weights.shape[2:] != (c0, c0)):
            return j, "width-change"
        if trunk_vmem_bytes(layers[i:j + 1], in_shape) > budget:
            return j, "vmem-budget"
        j += 1
    return j, "end"


def plan_stages(program: engine.CutieProgram, in_shape, n_stages: int,
                vmem_budget: int | None = None) -> list[Trunk]:
    """Partition a program into ``n_stages`` contiguous pipeline stages.

    Pipeline-parallel layer sharding (`repro.launch.cutie_mesh.
    PipelinedExecution`) maps the paper's layer-FIFO architecture onto a
    device ring: stage ``s`` owns layers ``[s*k, (s+1)*k)`` and streams
    its activations to stage ``s+1`` via ``ppermute``.  The SPMD ring
    carries ONE fixed-shape activation buffer, so every stage boundary
    must see the same tensor shape — the program must be a uniform
    trunk: identical weight shapes with Cin == Cout, stride 1, full
    padding, no merged pooling.  Violations raise with the offending
    layer named rather than silently running a wrong pipeline.

    Each returned :class:`Trunk` is one device's stage; ``fused`` /
    ``vmem_bytes`` record whether that stage would itself execute as a
    single fused megakernel on its device (the fused-under-mesh end
    state), via :func:`plan_segments` on the stage's slice.
    """
    layers = program.layers
    n_layers = len(layers)
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")
    if n_layers == 0 or n_layers % n_stages:
        raise ValueError(
            f"{n_layers} layers do not split into {n_stages} equal "
            f"pipeline stages; pad the program or pick a divisor of "
            f"{n_layers}")
    shape0 = tuple(layers[0].weights.shape)
    for i, instr in enumerate(layers):
        if (tuple(instr.weights.shape) != shape0
                or instr.weights.shape[2] != instr.weights.shape[3]):
            raise ValueError(
                f"layer {i}: weights {tuple(instr.weights.shape)} break "
                f"the uniform trunk (need Cin == Cout and shape "
                f"{shape0} everywhere); the pipeline ring carries one "
                f"fixed-shape activation buffer")
        if (instr.stride != (1, 1) or not instr.padding
                or instr.pool is not None):
            raise ValueError(
                f"layer {i}: pipeline-parallel stages need stride-1, "
                f"fully padded, pool-free layers (got stride="
                f"{instr.stride}, padding={instr.padding}, "
                f"pool={instr.pool}); spatial dims must survive every "
                f"stage boundary")
    k = n_layers // n_stages
    stages = []
    for s in range(n_stages):
        sub = engine.CutieProgram(layers[s * k:(s + 1) * k],
                                  program.instance)
        segs = plan_segments(sub, in_shape, vmem_budget)
        fused = len(segs) == 1 and segs[0].fused
        stages.append(Trunk(
            s * k, (s + 1) * k, fused=fused,
            vmem_bytes=segs[0].vmem_bytes if fused else 0,
            reason="" if fused else "/".join(
                dict.fromkeys(g.reason for g in segs if g.reason))))
    return stages


def plan_segments(program: engine.CutieProgram, in_shape,
                  vmem_budget: int | None = None) -> list[Trunk]:
    """Greedy maximal-trunk segmentation under a VMEM budget.

    ``in_shape`` is the (N, H, W, C) input the program will run on (the
    activation buffers scale with it).  Returns contiguous segments
    covering every layer exactly once, in order; runs that cannot trunk
    (length < 2) are grouped into per-layer segments so trunk
    boundaries — where inter-segment activations cross HBM — stay
    minimal.
    """
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    layers = program.layers
    shapes = segment_shapes(layers, in_shape[1:3])
    n = in_shape[0]

    segments: list[Trunk] = []
    pend = None                    # start of the open per-layer group
    pend_why: list[str] = []       # per-layer non-fusibility reasons
    i = 0

    def close_pend(upto: int):
        nonlocal pend
        why = "/".join(dict.fromkeys(pend_why))   # unique, in order
        segments.append(Trunk(pend, upto, fused=False, reason=why))
        pend = None
        pend_why.clear()

    while i < len(layers):
        h, w = shapes[i]
        shape_i = (n, h, w, layers[i].weights.shape[2])
        j, why = _trunk_stop(layers, i, shape_i, budget)
        if j - i >= 2:
            if pend is not None:
                close_pend(i)
            segments.append(Trunk(
                i, j, fused=True,
                vmem_bytes=trunk_vmem_bytes(layers[i:j], shape_i),
                reason=why if why == "vmem-budget" else ""))
            i = j
        else:
            # lone layer: the per-layer kernel is exactly equivalent
            pend = i if pend is None else pend
            pend_why.append("short-run" if why == "end" else why)
            i += 1
    if pend is not None:
        close_pend(len(layers))
    return segments
