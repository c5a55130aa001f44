"""Device-mesh execution for compiled CUTIE programs.

CUTIE's core argument (paper §III) is that completely unrolling the
filter and feature-map loops onto parallel compute units maximizes data
re-use; Tridgell et al. show the same unrolling scales with the
available fabric.  This module is the multi-device analogue of adding
fabric: a compiled :class:`~repro.core.engine.CutieProgram` executes

* **data-parallel** over the batch axis (each device runs the whole
  program on a batch shard), and/or
* **filter-parallel** over each layer's output-channel (OCU) axis: the
  layer's weight/threshold tensors are split across devices, every
  device computes its slice of output channels, and the ternary
  activations are all-gathered between layers — the software analogue
  of scaling the OCU array itself, and/or
* **pipeline-parallel** over the *layer* axis: contiguous trunk
  segments (`repro.compiler.trunks.plan_stages`) are assigned one per
  device, and microbatched activations stream producer-to-consumer
  around a ``ppermute`` ring — the paper's layer-FIFO architecture
  (§III, Fig. 3) mapped onto a device ring instead of on-chip FIFOs.

Inter-device activations travel **packed at 5 trits/byte** by default
(`repro.core.codec`, paper §III-A): the producer packs in its shard
epilogue, the consumer decodes in its prologue, so the tensor crossing
the interconnect is 5x smaller than dense int8 trits — bit-identical,
since the codec is lossless.  ``packed_collectives=False`` restores the
dense exchange (for apples-to-apples measurement).

Everything is built on ``shard_map`` over a ``("data", "filter")`` mesh
so it runs on
CPU host-device meshes (``XLA_FLAGS=--xla_force_host_platform_device_
count=N``) and real accelerator meshes alike.  Sharded execution is
bit-identical to the single-device backends: batch shards are
independent, channel slices are independent, and padding is done with
zero weights / constant-zero thresholds that cannot perturb live
channels.

The front door is :class:`repro.pipeline.CutiePipeline`::

    pipe = CutiePipeline(prog, backend="ref", mesh="data:4,filter:2")
    y = pipe.run(x)        # any batch size; padded + cropped internally
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.core import codec, engine, folding

Array = jax.Array

DATA_AXIS = "data"
FILTER_AXIS = "filter"
LAYER_AXIS = "layer"
_AXES = (DATA_AXIS, FILTER_AXIS, LAYER_AXIS)


def _ceil_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


# ---------------------------------------------------------------------------
# Mesh specification
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """How many devices shard the batch (``data``), the output-channel
    / OCU (``filter``) and the pipeline-stage (``layer``) dimensions.

    Accepted spellings (see :meth:`parse`): an int (pure data
    parallelism), a ``"data:4,filter:2"`` / ``"layer:4"`` string, a
    dict, a (data, filter[, layer]) tuple, an existing MeshSpec, or a
    ``jax.sharding.Mesh`` with axes named ``data``/``filter``/``layer``.
    """

    data: int = 1
    filter: int = 1
    layer: int = 1

    def __post_init__(self):
        if self.data < 1 or self.filter < 1 or self.layer < 1:
            raise ValueError(
                f"mesh degrees must be >= 1, got data={self.data}, "
                f"filter={self.filter}, layer={self.layer}")
        if self.layer > 1 and self.filter > 1:
            raise NotImplementedError(
                "layer (pipeline) and filter (OCU) sharding do not "
                "compose yet; use layer with data parallelism only")

    @property
    def n_devices(self) -> int:
        return self.data * self.filter * self.layer

    @classmethod
    def parse(cls, spec) -> "MeshSpec":
        if isinstance(spec, cls):
            return spec
        if isinstance(spec, jax.sharding.Mesh):
            # Only the axis SIZES are taken; build() re-materializes the
            # mesh over default-ordered devices.  Pin specific devices by
            # constructing the pipeline's mesh-dependent state yourself.
            sizes = dict(zip(spec.axis_names, spec.devices.shape))
            unknown = set(sizes) - set(_AXES)
            if unknown:
                raise ValueError(
                    f"mesh axes {sorted(unknown)} unsupported; CUTIE "
                    f"meshes use {DATA_AXIS!r}/{FILTER_AXIS!r}/"
                    f"{LAYER_AXIS!r}")
            return cls(data=sizes.get(DATA_AXIS, 1),
                       filter=sizes.get(FILTER_AXIS, 1),
                       layer=sizes.get(LAYER_AXIS, 1))
        if isinstance(spec, int):
            return cls(data=spec)
        if isinstance(spec, dict):
            unknown = set(spec) - set(_AXES)
            if unknown:
                raise ValueError(f"unknown mesh axes {sorted(unknown)}")
            return cls(data=int(spec.get(DATA_AXIS, 1)),
                       filter=int(spec.get(FILTER_AXIS, 1)),
                       layer=int(spec.get(LAYER_AXIS, 1)))
        if isinstance(spec, (tuple, list)):
            if len(spec) not in (2, 3):
                raise ValueError(
                    f"tuple mesh spec must be (data, filter[, layer]), "
                    f"got {spec}")
            return cls(*(int(n) for n in spec))
        if isinstance(spec, str):
            sizes = {}
            for part in spec.split(","):
                part = part.strip()
                if not part:
                    continue
                if ":" not in part:
                    raise ValueError(
                        f"bad mesh spec part {part!r} in {spec!r}; "
                        "expected 'axis:N'")
                axis, _, n = part.partition(":")
                axis = axis.strip()
                if axis not in _AXES:
                    raise ValueError(
                        f"unknown mesh axis {axis!r} in {spec!r}")
                sizes[axis] = int(n)
            return cls(data=sizes.get(DATA_AXIS, 1),
                       filter=sizes.get(FILTER_AXIS, 1),
                       layer=sizes.get(LAYER_AXIS, 1))
        raise TypeError(f"cannot parse a mesh spec from {type(spec).__name__}")

    def build(self) -> jax.sharding.Mesh:
        """Materialize the (data, filter, layer) device mesh."""
        avail = jax.device_count()
        if self.n_devices > avail:
            raise ValueError(
                f"mesh {self} needs {self.n_devices} devices but jax sees "
                f"{avail}; on CPU, set XLA_FLAGS=--xla_force_host_platform_"
                f"device_count={self.n_devices} before jax initializes")
        return jax.make_mesh((self.data, self.filter, self.layer), _AXES,
                             axis_types=(AxisType.Auto,) * len(_AXES))

    def __str__(self) -> str:
        s = f"{DATA_AXIS}:{self.data},{FILTER_AXIS}:{self.filter}"
        if self.layer > 1:
            s += f",{LAYER_AXIS}:{self.layer}"
        return s


# ---------------------------------------------------------------------------
# Filter-dimension program padding + slicing
# ---------------------------------------------------------------------------


def _pad_thresholds(th: folding.ChannelThresholds,
                    cout_pad: int) -> folding.ChannelThresholds:
    """Extend per-channel thresholds with constant-zero padding channels."""
    n = cout_pad - th.t_lo.shape[0]
    if n == 0:
        return th
    return folding.ChannelThresholds(
        t_lo=jnp.pad(th.t_lo, (0, n)),
        t_hi=jnp.pad(th.t_hi, (0, n)),
        flip=jnp.pad(th.flip, (0, n)),
        const=jnp.pad(th.const, (0, n)),
        is_const=jnp.pad(th.is_const, (0, n), constant_values=True),
    )


def _pad_instr(instr: engine.LayerInstr, cin_pad: int,
               cout_pad: int) -> engine.LayerInstr:
    """Zero-pad a layer to (cin_pad, cout_pad) channels, bit-exactly.

    Padded input channels meet zero weights (no contribution to the
    accumulator); padded output channels are constant-zero (is_const),
    so downstream layers see exact zeros there.
    """
    k, _, cin, cout = instr.weights.shape
    if (cin, cout) == (cin_pad, cout_pad):
        return instr
    w = jnp.pad(instr.weights,
                ((0, 0), (0, 0), (0, cin_pad - cin), (0, cout_pad - cout)))
    return dataclasses.replace(
        instr, weights=w, thresholds=_pad_thresholds(instr.thresholds,
                                                     cout_pad))


def _slice_instr(instr: engine.LayerInstr, shard: int,
                 n_shards: int) -> engine.LayerInstr:
    """One device's output-channel slice of a (padded) layer."""
    cout = instr.weights.shape[-1]
    assert cout % n_shards == 0, (cout, n_shards)
    cs = cout // n_shards
    lo, hi = shard * cs, (shard + 1) * cs
    th = instr.thresholds
    return dataclasses.replace(
        instr,
        weights=instr.weights[..., lo:hi],
        thresholds=folding.ChannelThresholds(
            t_lo=th.t_lo[lo:hi], t_hi=th.t_hi[lo:hi], flip=th.flip[lo:hi],
            const=th.const[lo:hi], is_const=th.is_const[lo:hi]))


def pad_program_for_filter(program: engine.CutieProgram, n_shards: int, *,
                           pad_input: bool = False
                           ) -> tuple[list, int, int]:
    """Pad every layer so each Cout divides ``n_shards``.

    Each layer's Cout is rounded up to a multiple of ``n_shards``; the
    next layer's Cin grows to match (zero weights).  With ``pad_input``
    (used to keep uniform programs scannable), layer 0's Cin is padded
    to its own padded Cout.  Returns ``(padded_layers,
    input_channel_pad, final_out_channels)`` — the caller zero-pads
    input activations by ``input_channel_pad`` channels and crops the
    final output back to ``final_out_channels``.
    """
    padded, in_pad = [], 0
    cin_pad = None
    for i, instr in enumerate(program.layers):
        _, _, cin, cout = instr.weights.shape
        cout_pad = _ceil_to(cout, n_shards)
        if i == 0:
            cin_pad = cout_pad if (pad_input and cout_pad >= cin) else cin
            in_pad = cin_pad - cin
        padded.append(_pad_instr(instr, cin_pad, cout_pad))
        cin_pad = cout_pad
    final = program.layers[-1].weights.shape[-1] if program.layers else 0
    return padded, in_pad, final


# ---------------------------------------------------------------------------
# Packed-trit collectives
# ---------------------------------------------------------------------------


def packed_all_gather(y: Array, axis_name: str, degree: int) -> Array:
    """All-gather trit activations along their channel axis, on the wire
    as 5-trits/byte packed bytes.

    The producer packs its local shard (`codec.pack_trits`), the byte
    streams are all-gathered, and the consumer decodes each peer's
    bytes back to trits — bit-identical to a dense
    ``all_gather(axis=-1, tiled=True)`` (the codec is lossless and
    shard ``f`` holds channels ``[f*Cs, (f+1)*Cs)``), with 5x less
    inter-device traffic.  Per-shard trailing pad trits (to a multiple
    of 5) are dropped by the decode.
    """
    if degree == 1:
        return y
    n = int(np.prod(y.shape))
    packed = codec.pack_trits(y)                          # (ceil(n/5),)
    gathered = jax.lax.all_gather(packed, axis_name)      # (F, ceil(n/5))
    parts = jax.vmap(lambda b: codec.unpack_trits(b, n))(gathered)
    # (F, N, H, W, Cs) -> (N, H, W, F*Cs): channel blocks in shard order
    parts = parts.reshape((degree,) + y.shape)
    return jnp.moveaxis(parts, 0, -2).reshape(
        y.shape[:-1] + (degree * y.shape[-1],))


def _exchange_bytes(shape, degree: int, packed: bool) -> int:
    """Bytes one device RECEIVES in one all-gather of an int8 tensor of
    ``shape`` sharded ``degree`` ways (its own shard does not cross the
    wire)."""
    if degree <= 1:
        return 0
    n = int(np.prod(shape))
    per_shard = codec.packed_size(n) if packed else n
    return (degree - 1) * per_shard


# ---------------------------------------------------------------------------
# Sharded whole-program execution
# ---------------------------------------------------------------------------


class ShardedExecution:
    """shard_map'd whole-program execution strategy for a pipeline.

    Owns the device mesh, the filter-padded program, and the per-device
    lowered weight shards (one backend ``lower`` per filter shard,
    stacked on a leading device axis that ``shard_map`` splits).  The
    built callable has the same ``(lowered, x) -> (out, records)``
    contract as the pipeline's single-device builder, so the pipeline's
    jit cache and run loop are shared.
    """

    def __init__(self, program: engine.CutieProgram, backend,
                 spec: MeshSpec, *, scan: bool = False,
                 packed: bool = True):
        self.spec = spec
        self.mesh = spec.build()
        self.backend = backend
        self.packed = packed
        f = spec.filter
        layers, self.in_channel_pad, self.out_channels = \
            pad_program_for_filter(program, f, pad_input=scan)
        # Static per-shard metadata (every shard has identical shapes).
        self.shard_instrs = [_slice_instr(l, 0, f) for l in layers]
        # Lowered arrays: leading axis = filter shard, split by shard_map.
        self.lowered = [
            jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[backend.lower(_slice_instr(l, d, f))
                           for d in range(f)])
            for l in layers]
        self.scannable = scan and self._shards_uniform()

    def _shards_uniform(self) -> bool:
        """Scannable after padding: identical per-shard layer shapes and
        a carry whose channel count survives the all-gather."""
        instrs = self.shard_instrs
        if not instrs:
            return False
        shape0 = tuple(instrs[0].weights.shape)
        for instr in instrs:
            if (tuple(instr.weights.shape) != shape0
                    or instr.stride != (1, 1)
                    or not instr.padding
                    or instr.pool is not None):
                return False
        # carry: Cin == gathered channels == filter_degree * shard Cout
        return shape0[2] == self.spec.filter * shape0[3]

    # -- batch/channel padding on the host ---------------------------------

    def pad_inputs(self, x: Array) -> Array:
        """Pad batch to a multiple of the data degree and input channels
        for filter-padded layer 0; both pads are exact no-ops."""
        n = x.shape[0]
        n_pad = _ceil_to(max(n, 1), self.spec.data)
        pads = [(0, n_pad - n), (0, 0), (0, 0), (0, self.in_channel_pad)]
        if any(p != (0, 0) for p in pads):
            x = jnp.pad(x, pads)
        return x

    def crop(self, out: Array, n: int) -> Array:
        """Undo batch and output-channel padding."""
        return out[:n, ..., :self.out_channels]

    # -- traced program ------------------------------------------------------

    def collective_bytes(self, in_shape) -> dict:
        """Per-device inter-layer collective traffic for one run, in
        bytes, dense vs 5-trits/byte packed — the quantity the packed
        exchange divides by ~5.  ``in_shape`` is the (padded) global
        (N, H, W, C) input; batch splits over the data axis first."""
        n = _ceil_to(max(in_shape[0], 1), self.spec.data) // self.spec.data
        h, w = in_shape[1], in_shape[2]
        dense = packed = 0
        for instr in self.shard_instrs:
            oh, ow = engine.layer_out_dims(
                instr.kernel_size, instr.stride, instr.padding, instr.pool,
                h, w)
            shard = (n, oh, ow, instr.weights.shape[-1])
            dense += _exchange_bytes(shard, self.spec.filter, packed=False)
            packed += _exchange_bytes(shard, self.spec.filter, packed=True)
            h, w = oh, ow
        return {"dense": dense, "packed": packed,
                "on_wire": packed if self.packed else dense}

    def build(self):
        """The jitted sharded whole-program callable."""
        backend, instrs = self.backend, self.shard_instrs
        filter_degree, packed = self.spec.filter, self.packed

        def gather(y):
            if packed:
                return packed_all_gather(y, FILTER_AXIS, filter_degree)
            return jax.lax.all_gather(y, FILTER_AXIS, axis=-1, tiled=True)

        if self.scannable:
            instr0 = instrs[0]

            def mapped(lowered, x):
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *lowered)

                def body(cur, lw):
                    shard = jax.tree.map(lambda a: a[0], lw)
                    return gather(backend.apply(shard, cur, instr0)), {}

                return jax.lax.scan(body, x, stacked)
        else:
            def mapped(lowered, x):
                cur = x
                for lw, instr in zip(lowered, instrs):
                    shard = jax.tree.map(lambda a: a[0], lw)
                    cur = gather(backend.apply(shard, cur, instr))
                return cur, [{} for _ in instrs]

        fn = jax.shard_map(
            mapped, mesh=self.mesh,
            in_specs=([P(FILTER_AXIS)] * len(self.lowered), P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS), P()),
            check_vma=False)       # gathered outputs are filter-replicated
        return jax.jit(fn)

    def __repr__(self) -> str:
        return (f"ShardedExecution(mesh={self.spec}, "
                f"backend={self.backend.name!r}, scan={self.scannable}, "
                f"packed={self.packed})")


# ---------------------------------------------------------------------------
# Pipeline-parallel layer sharding
# ---------------------------------------------------------------------------


class PipelinedExecution:
    """Pipeline-parallel execution: one trunk segment per device, on a
    ``ppermute`` ring — the paper's layer-FIFO across devices.

    The program is carved into ``spec.layer`` equal contiguous stages
    (`repro.compiler.trunks.plan_stages`, which also enforces the
    uniform-trunk shape the SPMD ring needs).  Each device holds only
    its stage's weights; the local batch shard is split into
    ``microbatches`` microbatches that flow through the ring
    GPipe-style: at step ``t``, stage ``s`` processes microbatch
    ``t - s`` and hands its activations to stage ``s + 1`` via
    ``ppermute`` — packed at 5 trits/byte unless ``packed=False``.
    With S stages and M microbatches the schedule runs ``M + S - 1``
    steps, so the pipeline bubble is ``(S-1)/(M+S-1)`` of each stage's
    time (see :meth:`schedule_stats`).

    Composes with data parallelism (batch shards over the ``data`` axis
    flow through per-data-shard rings); filter sharding does not compose
    yet (`MeshSpec` rejects it).  Bit-identical to single-device
    execution: microbatching only re-chunks the batch, the ring only
    moves tensors, and the codec is lossless.
    """

    def __init__(self, program: engine.CutieProgram, backend,
                 spec: MeshSpec, *, microbatches: int | None = None,
                 packed: bool = True):
        from repro.compiler import trunks

        self.spec = spec
        self.mesh = spec.build()
        self.backend = backend
        self.packed = packed
        self.n_stages = spec.layer
        self.microbatches = microbatches or 2 * self.n_stages
        if self.microbatches < 1:
            raise ValueError(
                f"microbatches must be >= 1, got {self.microbatches}")
        # stage planning doubles as uniform-trunk validation; the
        # activation-buffer shape is filled in per run, so plan with a
        # nominal single-image input here (re-planned in stats if asked)
        c = program.layers[0].weights.shape[2]
        self.stages = trunks.plan_stages(
            program, (1, 8, 8, c), self.n_stages)
        self.layers_per_stage = len(self.stages[0])
        self.program = program
        self.out_channels = program.layers[-1].weights.shape[-1]
        self.in_channel_pad = 0
        # lowered weights: (S, k, ...) — stage axis split by shard_map,
        # layer axis scanned inside each stage
        per_layer = [backend.lower(i) for i in program.layers]
        k = self.layers_per_stage
        self.lowered = jax.tree.map(
            lambda *xs: jnp.stack(xs).reshape((self.n_stages, k)
                                              + xs[0].shape),
            *per_layer)
        self.scannable = True

    # -- schedule accounting -------------------------------------------------

    def schedule_stats(self) -> dict:
        """Static GPipe-schedule accounting: per-stage occupancy (the
        fraction of ring steps each stage computes a live microbatch)
        and the bubble fraction (fill+drain idle time)."""
        s, m = self.n_stages, self.microbatches
        steps = m + s - 1
        return {
            "stages": s,
            "microbatches": m,
            "layers_per_stage": self.layers_per_stage,
            "ring_steps": steps,
            "per_stage_occupancy": [m / steps] * s,
            "bubble_fraction": (s - 1) / steps,
        }

    def collective_bytes(self, in_shape) -> dict:
        """Per-device ring traffic for one run (the final masked
        output reduction over the layer axis is counted separately as
        ``reduce``)."""
        n = self.pad_inputs_to(in_shape[0]) // self.spec.data
        mb = n // self.microbatches
        shape = (mb,) + tuple(in_shape[1:])
        sz = int(np.prod(shape))
        steps = self.microbatches + self.n_stages - 1
        return {
            "dense": steps * sz,
            "packed": steps * codec.packed_size(sz),
            "on_wire": steps * (codec.packed_size(sz) if self.packed
                                else sz),
            "reduce": 4 * n * int(np.prod(in_shape[1:])),
        }

    # -- batch padding on the host -------------------------------------------

    def pad_inputs_to(self, n: int) -> int:
        """Batches pad to data_degree * microbatches so every data shard
        splits into whole microbatches."""
        return _ceil_to(max(n, 1), self.spec.data * self.microbatches)

    def pad_inputs(self, x: Array) -> Array:
        n_pad = self.pad_inputs_to(x.shape[0])
        if n_pad != x.shape[0]:
            x = jnp.pad(x, [(0, n_pad - x.shape[0])] + [(0, 0)] * 3)
        return x

    def crop(self, out: Array, n: int) -> Array:
        return out[:n]

    # -- traced program ------------------------------------------------------

    def build(self):
        """The jitted pipelined whole-program callable."""
        backend = self.backend
        instr0 = self.program.layers[0]
        s_deg, m = self.n_stages, self.microbatches
        packed = self.packed
        perm = [(i, (i + 1) % s_deg) for i in range(s_deg)]

        def ring_shift(y):
            if not packed:
                return jax.lax.ppermute(y, LAYER_AXIS, perm)
            b = codec.pack_trits(y)
            b = jax.lax.ppermute(b, LAYER_AXIS, perm)
            return codec.unpack_trits(b, int(np.prod(y.shape))).reshape(
                y.shape)

        def mapped(lowered, x):
            # lowered: this stage's (1, k, ...) slice; x: local batch shard
            stage_stack = jax.tree.map(lambda a: a[0], lowered)
            sid = jax.lax.axis_index(LAYER_AXIS)
            mb = x.shape[0] // m
            xm = x.reshape((m, mb) + x.shape[1:])

            def run_stage(a):
                def body(cur, lw):
                    return backend.apply(lw, cur, instr0), None

                out, _ = jax.lax.scan(body, a, stage_stack)
                return out

            state0 = jnp.zeros((mb,) + x.shape[1:], x.dtype)
            outbuf0 = jnp.zeros((m, mb) + x.shape[1:], x.dtype)

            def step(carry, t):
                state, outbuf = carry
                # stage 0 injects microbatch t (its ring input is the
                # wrapped-around tail of the ring: garbage by design);
                # past the last microbatch it recomputes xm[m-1], whose
                # results drain past the end of the schedule unused
                inj = jax.lax.dynamic_index_in_dim(
                    xm, jnp.clip(t, 0, m - 1), 0, keepdims=False)
                cur = jnp.where(sid == 0, inj, state)
                y = run_stage(cur)
                # the last stage completed microbatch t - (S-1)
                oidx = jnp.clip(t - (s_deg - 1), 0, m - 1)
                valid = (sid == s_deg - 1) & (t >= s_deg - 1)
                prev = jax.lax.dynamic_index_in_dim(outbuf, oidx, 0,
                                                    keepdims=False)
                outbuf = jax.lax.dynamic_update_index_in_dim(
                    outbuf, jnp.where(valid, y, prev), oidx, 0)
                return (ring_shift(y), outbuf), None

            (_, outbuf), _ = jax.lax.scan(
                step, (state0, outbuf0), jnp.arange(m + s_deg - 1))
            # results live on the last stage only; a masked psum
            # replicates them (every other stage contributes zeros, so
            # the sum is exact — int32 to keep the reduce dtype-safe)
            outbuf = jnp.where(sid == s_deg - 1, outbuf.astype(jnp.int32),
                               0)
            out = jax.lax.psum(outbuf, LAYER_AXIS).astype(x.dtype)
            return out.reshape((x.shape[0],) + x.shape[1:]), {}

        fn = jax.shard_map(
            mapped, mesh=self.mesh,
            in_specs=(P(LAYER_AXIS), P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS), P()),
            check_vma=False)        # outputs are layer/filter-replicated
        return jax.jit(fn)

    def __repr__(self) -> str:
        return (f"PipelinedExecution(mesh={self.spec}, "
                f"backend={self.backend.name!r}, "
                f"stages={self.n_stages}, "
                f"microbatches={self.microbatches}, "
                f"packed={self.packed})")
