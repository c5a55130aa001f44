"""Smoke run of the CUTIE serving path on the TPU, checked bit for bit.

    python chip_smoke.py [--seed 0]     # one chip: CNN on pallas/packed/fused,
                                        # then the LLM decode loop
    python chip_smoke.py --mesh         # four chips: the mesh paths only

The CNN phase builds the paper's Table III network at its published
widths (126-channel thermometer input, 8 x 128-wide 3x3 convs with three
max-pools and a 4x4 avg-pool, and the FC head on the OCU), lowers it with
``to_graph(include_head=True)`` and ``CutiePipeline.compile``, and serves
seeded images through ``CutieEngine`` at batch 1 and in buckets of 8 on
each Pallas backend.  Every output must equal, bit for bit, the same
program run by the ``ref`` backend on the CPU.  Trained weights are not
in the repository: weights come from ``init_params(seed)``, and the
batch-norm parameters are drawn from the same seed so that per-channel
flips and constant channels (every branch of the kernels' epilogue)
occur.  ``--mesh`` serves the same CNN on ``data:4`` and
``data:2,filter:2`` and a uniform 8-layer 128-wide trunk on ``layer:4``,
with packed and with dense collectives.

The script refuses to run anywhere but on a TPU and stops at the first
failed check.  Its last line on stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
JAX's persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, or else in ``.jax_cache`` next to this file.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
BACKENDS = ("pallas", "packed", "fused")
BUCKETS = (1, 8)
N_IMAGES = 16                 # one round of bucket-8 batches
MESHES = ("data:4", "data:2,filter:2")

# The bit-exact reference runs on the host CPU next to the chip.
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def log(*args) -> None:
    print(*args, flush=True)


def require_tpu(n_chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX's default platform is "
            f"{devs[0].platform!r} ({devs[0].device_kind}); nothing was run")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, "
                         f"found {len(devs)}")
    return devs


def use_compile_cache() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# the paper's CNN, seeded
# ---------------------------------------------------------------------------


def cnn_graph(seed: int):
    """Table III at published widths, with the head; host (numpy) arrays."""
    from repro.configs.cutie_cnn import CONFIG
    from repro.models import cutie_cnn

    kp, kb = jax.random.split(jax.random.PRNGKey(seed))
    params = cutie_cnn.init_params(CONFIG, kp)
    layers = []
    for lp, k in zip(params["layers"],
                     jax.random.split(kb, len(params["layers"]))):
        k1, k2, k3 = jax.random.split(k, 3)
        c = lp["gamma"].shape[0]
        gamma = 1.0 + 0.5 * jax.random.normal(k1, (c,))
        gamma = jnp.where(jax.random.bernoulli(k3, 0.03, (c,)), 0.0, gamma)
        layers.append(dict(lp, gamma=gamma,
                           beta=0.5 * jax.random.normal(k2, (c,))))
    params = jax.tree.map(np.asarray, dict(params, layers=layers))
    return CONFIG, cutie_cnn.to_graph(params, CONFIG, include_head=True)


def compile_cnn(graph, backend, **kw):
    """The one front door.  ``optimize=False`` keeps every layer at its
    published width (the exact sparsity passes would drop constant
    channels); the layer FIFO holds the 8 convs plus the head."""
    from repro.core import engine
    from repro.pipeline import CutiePipeline

    inst = dataclasses.replace(engine.GF22_SCM, n_layers=9)
    return CutiePipeline.compile(graph, instance=inst, backend=backend,
                                 optimize=False, **kw)


def images(cfg, seed: int, n: int) -> np.ndarray:
    """n seeded RGB images, thermometer-encoded to (n, 32, 32, 126) trits."""
    from repro.core.thermometer import encode_image_ternary

    rgb = np.random.default_rng(seed).random(
        (n, cfg.img_hw, cfg.img_hw, 3), np.float32)
    enc = jax.vmap(lambda im: encode_image_ternary(im, cfg.thermometer_m))
    return np.asarray(enc(jnp.asarray(rgb)), np.int8)


def cpu_reference(build, x: np.ndarray) -> np.ndarray:
    """Build a ``ref`` pipeline on the host CPU, run it on x there, and
    prove that it ran there."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        out = build().run(jnp.asarray(x))
    check(out.devices() == {cpu}, f"reference ran on {out.devices()}")
    return np.asarray(out)


def serve(eng, model: str, xs: np.ndarray) -> tuple[list, float]:
    t0 = time.perf_counter()
    handles = [eng.submit(x, model=model) for x in xs]
    eng.run()
    return handles, time.perf_counter() - t0


def check_handles(handles, want: np.ndarray, what: str) -> None:
    from repro.serving import RequestStatus

    for i, h in enumerate(handles):
        req = h.request
        check(h.status is RequestStatus.DONE and req.error is None,
              f"{what}: request {i} ended {h.status} ({req.error!r})")
        check(np.array_equal(np.asarray(req.result), want[i]),
              f"{what}: request {i} differs from the CPU ref backend")


def check_engine(eng, model: str, n_done: int, padded: dict,
                 what: str) -> None:
    st = eng.stats()
    check(st["n_failed"] == 0 and st["n_done"] == n_done,
          f"{what}: {st['n_done']} done, {st['n_failed']} failed")
    check(st["faults"]["n_retries"] == 0, f"{what}: {st['faults']}")
    got = collections.Counter(b["padded"] for b in eng.batches
                              if b["model"] == model)
    check(got == collections.Counter(padded),
          f"{what}: batch sizes {dict(got)} != {padded}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def cnn_phase(seed: int) -> None:
    cfg, graph = cnn_graph(seed)
    x = images(cfg, seed, N_IMAGES + 1)
    want = cpu_reference(lambda: compile_cnn(graph, "ref"), x)
    log(f"reference: ref backend on cpu, output {want.shape}, "
        f"{int((want == 0).sum())}/{want.size} zero trits")
    for name in BACKENDS:
        pipe = compile_cnn(graph, name)
        check(pipe.backend.interpret is False,
              f"{name}: backend would run in interpret mode")
        for n in BUCKETS:
            log(f"{name} plan at batch {n}: "
                f"{json.dumps(pipe.execution_plan((n, *x.shape[1:])))}")
        eng = pipe.engine("deadline", buckets=BUCKETS)
        h1, t1 = serve(eng, "default", x[:1])
        h8, t8 = serve(eng, "default", x[1:])
        hs, ts = serve(eng, "default", x)
        check_handles(h1 + h8, want, name)
        check_handles(hs, want, name)
        check_engine(eng, "default", 2 * len(x), {1: 2, 8: 4}, name)
        check(pipe.n_jit_variants == len(BUCKETS),
              f"{name}: {pipe.n_jit_variants} jit variants")
        secs = collections.defaultdict(list)
        for b in eng.batches:
            secs[b["padded"]].append(b["seconds"])
        log(f"{name}: {2 * len(x)} requests DONE, bit-identical to ref; "
            f"batch seconds {dict(secs)} (first of each size compiles); "
            f"round seconds: batch-1 {t1:.4f}, 2x8 {t8:.4f}, "
            f"steady 2x8+1 {ts:.4f}")


def llm_phase(seed: int) -> None:
    """Greedy decode on the chip; paged and contiguous outputs agree."""
    import repro.configs as configs
    from repro.models import transformer as TF
    from repro.models.config import reduce_for_smoke
    from repro.serving import (CutieEngine, LLMExecutor, RequestStatus,
                               ServerConfig)

    cfg = reduce_for_smoke(configs.get("llama3.2-1b"))
    params = TF.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, int(n)).astype(np.int32)
               for n in rng.integers(8, 24, 4)]
    outs = {}
    for paged in (True, False):
        # one block per kv chunk keeps paged prefill on the same chunk grid
        scfg = ServerConfig(paged=paged, n_slots=4, max_new_tokens=8,
                            max_len=128, block_size=cfg.attn_kv_chunk)
        eng = CutieEngine("fcfs")
        eng.register("llm", LLMExecutor(params, cfg, scfg))
        handles, secs = serve(eng, "llm", prompts)
        for i, h in enumerate(handles):
            check(h.status is RequestStatus.DONE
                  and h.request.error is None,
                  f"llm paged={paged}: request {i} ended {h.status} "
                  f"({h.request.error!r})")
        outs[paged] = [np.asarray(h.request.result).tolist()
                       for h in handles]
        check(all(len(o) == scfg.max_new_tokens for o in outs[paged]),
              f"llm paged={paged}: lengths {[len(o) for o in outs[paged]]}")
        log(f"llm paged={paged}: 4 greedy requests DONE in {secs:.3f}s")
    check(outs[True] == outs[False],
          f"llm: paged {outs[True]} != contiguous {outs[False]}")
    log("llm: paged and contiguous outputs identical")


def uniform_trunk(seed: int):
    """8 padded 3x3 128->128 layers, no pools: the layer-mesh workload."""
    from repro import compiler

    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 16)
    g = compiler.Graph(in_channels=128, in_hw=(32, 32))
    for i in range(8):
        w = np.asarray(jax.random.normal(keys[2 * i], (3, 3, 128, 128)))
        beta = np.asarray(0.5 * jax.random.normal(keys[2 * i + 1], (128,)))
        g.conv(w, {"beta": beta})
    return g


def mesh_phase(seed: int) -> None:
    from repro.pipeline import CutiePipeline

    cfg, graph = cnn_graph(seed)
    x = images(cfg, seed, N_IMAGES)
    want = cpu_reference(lambda: compile_cnn(graph, "ref"), x)
    trunk = compile_cnn(uniform_trunk(seed), "ref").program
    tx = np.random.default_rng(seed).integers(
        -1, 2, (N_IMAGES, 32, 32, 128)).astype(np.int8)
    twant = cpu_reference(lambda: CutiePipeline(trunk, backend="ref"), tx)
    cases = [(m, graph, x, want) for m in MESHES]
    cases.append(("layer:4", None, tx, twant))
    for mesh, g, xs, ref_out in cases:
        for packed in (True, False):
            what = f"mesh {mesh} packed={packed}"
            kw = dict(mesh=mesh, packed_collectives=packed)
            pipe = (compile_cnn(g, "pallas", **kw) if g is not None else
                    CutiePipeline(trunk, backend="pallas", **kw))
            check(pipe.backend.interpret is False,
                  f"{what}: backend would run in interpret mode")
            log(f"{what} plan: {json.dumps(pipe.execution_plan())}")
            eng = pipe.engine("fcfs", buckets=(8,))
            handles, secs = serve(eng, "default", xs)
            check_handles(handles, ref_out, what)
            bucket = eng.registry["default"].buckets[-1]
            check_engine(eng, "default", len(xs),
                         {bucket: -(-len(xs) // bucket)}, what)
            log(f"{what}: {len(xs)} requests DONE, bit-identical to ref, "
                f"{secs:.3f}s with compile")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: data, data x filter and layer meshes")
    args = ap.parse_args(argv)

    devs = require_tpu(4 if args.mesh else 1)
    use_compile_cache()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    log(f"device: {devs[0].device_kind} x {len(devs)}, jax {jax.__version__}")
    phases = [mesh_phase] if args.mesh else [cnn_phase, llm_phase]
    for phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        log(f"{phase.__name__}: ok in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
