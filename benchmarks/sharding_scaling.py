"""Multi-device scaling of CutiePrograms: throughput, traffic, pipelining.

CUTIE's unrolling argument (paper §III; Tridgell et al.) says throughput
scales with the compute fabric you unroll onto.  This benchmark measures
the software analogue over a host-device mesh via
``CutiePipeline(mesh=...)``:

* **data-parallel** batch sharding on the CIFAR CutieProgram (paper
  Table III layout, width-reduced for CPU budgets), devices 1..8,
* **filter-dimension** (OCU/output-channel) sharding, packed
  5-trits/byte collectives vs dense int8 — both wall-clock and the
  analytic per-device all-gather traffic (`collective_bytes`), whose
  dense/packed ratio is ~5x by construction and host-invariant,
* **pipeline-parallel** layer sharding on a uniform 8-layer trunk (the
  CIFAR program has pools, which the SPMD ring cannot carry): one stage
  per device, microbatches streamed through a ``ppermute`` ring,
  including a batch that does not divide the microbatch count.

Every sharded output is checked bit-exact against the unsharded ``ref``
oracle; failures raise, so CI fails on correctness, never on absolute
speed (shared runners).

Gating under ``run.py --compare`` (see ``SPEED_CHECKS`` /
``THROUGHPUT_METRICS`` below) with a documented **host-core guard**:

* the packed-traffic ratios and bit-exactness are host-invariant and
  gate unconditionally;
* the wall-clock scaling check ``scaling_4x_8dev`` and the gated
  ``speedup_vs_1dev.8`` metric need real host parallelism — the check
  is recorded as ``None`` (with the reason under ``checks_guard``) on
  hosts with fewer than 8 cores, and the metric diff is implicitly
  guarded because ``config`` embeds ``host_cores``: ``run.py`` skips
  metric deltas whenever the baseline config differs, so a 2-core CI
  runner never diffs speedups against an 8-core baseline.

The measurement runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=<N>`` and
``JAX_PLATFORMS=cpu``, so it works no matter how the parent process
initialized jax and never reaches for an accelerator the parent may
hold.  Every number it reports is therefore a CPU number (emulated
host devices), not a chip number.

    PYTHONPATH=src python benchmarks/sharding_scaling.py [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

N_DEVICES = 8
_FLAG = "--xla_force_host_platform_device_count"

#: Boolean ``res["checks"]`` entries enforced by ``run.py --compare``.
#: ``scaling_4x_8dev`` is None (guarded, see ``checks_guard``) on hosts
#: with < 8 cores; the traffic ratios are analytic and always evaluate.
SPEED_CHECKS = ("scaling_4x_8dev", "packed_traffic_5x_filter",
                "packed_traffic_5x_layer")

#: Gated metrics (>20% regression fails --compare).  The traffic ratios
#: are host-invariant; the speedup is host-dependent but guarded by the
#: config check — ``config.host_cores`` differs across runner classes,
#: and run.py skips the diff on any config mismatch.
THROUGHPUT_METRICS = {
    "traffic.filter.dense_over_packed": "higher",
    "traffic.layer.dense_over_packed": "higher",
    "speedup_vs_1dev.8": "higher",
}

#: Noise-prone absolute numbers: deltas printed, never gating.
INFO_METRICS = {
    "throughput_img_s.1": "higher",
    "throughput_img_s.8": "higher",
    "filter_throughput_img_s.packed_2": "higher",
    "layer_throughput_img_s.4": "higher",
}


def _config(smoke: bool) -> dict:
    return {
        "devices": [1, 2, 4, 8],
        "width": 8 if smoke else 16,
        "thermometer_m": 2 if smoke else 4,
        "batch": 16 if smoke else 32,
        "reps": 2 if smoke else 3,
        "filter_degrees": [2] if smoke else [2, 4],
        "layer_degrees": [2, 4] if smoke else [2, 4, 8],
        "trunk_layers": 8,
        "smoke": smoke,
    }


# ---------------------------------------------------------------------------
# Measurement (runs inside the subprocess — 8 host devices forced)
# ---------------------------------------------------------------------------


def _uniform_trunk(width: int, n_layers: int):
    """A uniform stride-1/padded/pool-free trunk — the shape pipeline-
    parallel stages require (the CIFAR program's pools break it)."""
    import jax
    import jax.numpy as jnp

    from repro.core import engine

    keys = jax.random.split(jax.random.PRNGKey(7), n_layers)
    instrs = []
    for k in keys:
        k1, k2 = jax.random.split(k)
        w = jax.random.normal(k1, (3, 3, width, width))
        bn = {"gamma": jax.random.normal(k2, (width,)) + 0.5,
              "beta": jnp.zeros((width,)), "mean": jnp.zeros((width,)),
              "var": jnp.ones((width,))}
        instrs.append(engine.compile_layer(w, bn))
    return engine.CutieProgram(
        instrs, engine.CutieInstance(n_i=width, n_o=width))


def _measure(cfg: dict) -> dict:
    import jax
    import numpy as np

    from repro.configs.cutie_cnn import CutieCNNConfig
    from repro.models import cutie_cnn
    from repro.pipeline import CutiePipeline, MeshSpec

    ccfg = CutieCNNConfig(width=cfg["width"],
                          thermometer_m=cfg["thermometer_m"])
    params = cutie_cnn.init_params(ccfg, jax.random.PRNGKey(0))
    prog = cutie_cnn.to_program(params, ccfg)

    rng = np.random.default_rng(0)
    batch = cfg["batch"]
    x = rng.integers(-1, 2, (batch, ccfg.img_hw, ccfg.img_hw,
                             ccfg.in_channels)).astype(np.int8)
    x_odd = x[: batch - 3]          # padding path: does not divide any mesh

    ref = CutiePipeline(prog, backend="ref")
    y_ref = np.asarray(ref.run(x))
    y_ref_odd = y_ref[: batch - 3]

    def timed(pipe, xb) -> float:
        jax.block_until_ready(pipe.run(xb))          # compile + warm
        best = float("inf")
        for _ in range(cfg["reps"]):
            t0 = time.perf_counter()
            jax.block_until_ready(pipe.run(xb))
            best = min(best, time.perf_counter() - t0)
        return best

    checks: dict = {}

    def bit_check(name: str, y, oracle, what: str):
        ok = bool((np.asarray(y) == oracle).all())
        checks[name] = ok
        if not ok:
            raise AssertionError(f"{what} differs from the ref oracle")

    # -- data-parallel batch sharding ---------------------------------------
    throughput = {}
    for d in cfg["devices"]:
        pipe = CutiePipeline(prog, backend="ref", mesh=MeshSpec(data=d))
        bit_check(f"bit_exact_data{d}", pipe.run(x), y_ref,
                  f"data-parallel output (mesh data:{d})")
        throughput[str(d)] = batch / timed(pipe, x)
    base = throughput["1"]
    speedup = {d: t / base for d, t in throughput.items()}

    # padding path: batch that does not divide the mesh
    pipe = CutiePipeline(prog, backend="ref",
                         mesh=MeshSpec(data=cfg["devices"][-1]))
    bit_check("bit_exact_padding", pipe.run(x_odd), y_ref_odd,
              "padded-batch sharded output")

    # -- filter sharding: packed vs dense collectives -----------------------
    filter_tp = {}
    traffic: dict = {}
    for f in cfg["filter_degrees"]:
        for packed in (True, False):
            pipe = CutiePipeline(prog, backend="ref",
                                 mesh=MeshSpec(filter=f),
                                 packed_collectives=packed)
            wire = "packed" if packed else "dense"
            bit_check(f"bit_exact_filter{f}_{wire}", pipe.run(x), y_ref,
                      f"filter-sharded output (mesh filter:{f}, {wire})")
            filter_tp[f"{wire}_{f}"] = batch / timed(pipe, x)
        bytes_ = pipe._sharded.collective_bytes(x.shape)
        traffic.setdefault("filter", {
            "dense_bytes": bytes_["dense"],
            "packed_bytes": bytes_["packed"],
            "dense_over_packed": bytes_["dense"] / bytes_["packed"],
        })
    checks["packed_traffic_5x_filter"] = (
        4.5 < traffic["filter"]["dense_over_packed"] <= 5.0)

    # -- pipeline-parallel layer sharding (uniform trunk) -------------------
    trunk = _uniform_trunk(cfg["width"], cfg["trunk_layers"])
    xt = rng.integers(-1, 2, (batch, ccfg.img_hw, ccfg.img_hw,
                              cfg["width"])).astype(np.int8)
    trunk_ref = CutiePipeline(trunk, backend="ref")
    yt_ref = np.asarray(trunk_ref.run(xt))
    layer_tp = {"1": batch / timed(trunk_ref, xt)}
    for ldeg in cfg["layer_degrees"]:
        pipe = CutiePipeline(trunk, backend="ref",
                             mesh=MeshSpec(layer=ldeg))
        bit_check(f"bit_exact_layer{ldeg}", pipe.run(xt), yt_ref,
                  f"pipeline-parallel output (mesh layer:{ldeg})")
        layer_tp[str(ldeg)] = batch / timed(pipe, xt)
        traffic.setdefault("layer", {})
        if ldeg == cfg["layer_degrees"][-1]:
            bytes_ = pipe._sharded.collective_bytes(xt.shape)
            traffic["layer"] = {
                "dense_bytes": bytes_["dense"],
                "packed_bytes": bytes_["packed"],
                "dense_over_packed": bytes_["dense"] / bytes_["packed"],
            }
            schedule = pipe._sharded.schedule_stats()
    checks["packed_traffic_5x_layer"] = (
        4.5 < traffic["layer"]["dense_over_packed"] <= 5.0)
    # microbatch padding path: batch that does not divide the microbatch
    # count (outputs must come back in submission order)
    pipe = CutiePipeline(trunk, backend="ref", mesh=MeshSpec(layer=2),
                         microbatches=3)
    bit_check("bit_exact_layer_padding", pipe.run(xt[: batch - 3]),
              yt_ref[: batch - 3], "microbatch-padded pipelined output")

    # -- wall-clock scaling check (host-core guarded) -----------------------
    n_cores = os.cpu_count() or 1
    top = str(cfg["devices"][-1])
    checks_guard = {}
    if n_cores >= 8:
        checks["scaling_4x_8dev"] = speedup[top] > 4.0
    else:
        checks["scaling_4x_8dev"] = None
        checks_guard["scaling_4x_8dev"] = (
            f"not evaluated: {n_cores} host cores < 8 — forced host "
            f"devices share cores, so wall-clock speedup cannot "
            f"materialize here; bit-exactness and the packed-traffic "
            f"ratios still gate")
    dev = jax.devices()
    return {
        "config": {**cfg, "host_cores": n_cores,
                   "layers": len(prog.layers)},
        "device": {"platform": dev[0].platform, "kind": dev[0].device_kind,
                   "count": len(dev)},
        "throughput_img_s": throughput,
        "speedup_vs_1dev": speedup,
        "filter_throughput_img_s": filter_tp,
        "layer_throughput_img_s": layer_tp,
        "traffic": traffic,
        "pipeline_schedule": schedule,
        "checks": checks,
        "checks_guard": checks_guard,
    }


# ---------------------------------------------------------------------------
# Harness entry points
# ---------------------------------------------------------------------------


def run(smoke: bool = False) -> dict:
    """Spawn the measurement under a forced 8-host-device CPU topology."""
    env = dict(os.environ)
    # Replace (not keep) any inherited host-device count: a parent that
    # exported a smaller value would otherwise break the 8-device mesh.
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(_FLAG)]
    env["XLA_FLAGS"] = " ".join(flags + [f"{_FLAG}={N_DEVICES}"])
    # Host devices only: a chip belongs to one process, and the parent
    # may hold it.
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    argv = [sys.executable, os.path.abspath(__file__), "--json"]
    if smoke:
        argv.append("--smoke")
    r = subprocess.run(argv, env=env, cwd=root, capture_output=True,
                       text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(
            f"sharding subprocess failed:\n{r.stdout}\n{r.stderr}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def report(res: dict) -> str:
    cfg = res["config"]
    dev = res["device"]
    lines = [
        "## Sharded multi-device scaling (CIFAR CutieProgram)",
        "",
        f"CPU numbers: {dev['count']} emulated {dev['platform']} host "
        "devices, not a chip measurement.",
        "",
        f"width={cfg['width']}, batch={cfg['batch']}, "
        f"{cfg['layers']} layers, {cfg['host_cores']} host cores",
        "",
        "| devices (data) | img/s | speedup |",
        "|---|---|---|",
    ]
    for d, tp in res["throughput_img_s"].items():
        lines.append(f"| {d} | {tp:.1f} | "
                     f"{res['speedup_vs_1dev'][d]:.2f}x |")
    lines += ["", "| filter shards (wire) | img/s |", "|---|---|"]
    for f, tp in res["filter_throughput_img_s"].items():
        lines.append(f"| {f} | {tp:.1f} |")
    lines += ["", "| pipeline stages (layer) | img/s |", "|---|---|"]
    for d, tp in res["layer_throughput_img_s"].items():
        lines.append(f"| {d} | {tp:.1f} |")
    sched = res["pipeline_schedule"]
    lines += [
        "",
        f"pipeline schedule: {sched['stages']} stages x "
        f"{sched['microbatches']} microbatches, "
        f"bubble {sched['bubble_fraction']:.1%}",
        "",
        "per-device all-gather / ring traffic (bytes, one run):",
    ]
    for axis, t in res["traffic"].items():
        lines.append(f"- {axis}: dense {t['dense_bytes']} -> packed "
                     f"{t['packed_bytes']} "
                     f"({t['dense_over_packed']:.2f}x smaller on the wire)")
    checks = ", ".join(f"{k}={v}" for k, v in res["checks"].items())
    lines += ["", f"checks: {checks}"]
    for k, why in res.get("checks_guard", {}).items():
        lines.append(f"guard[{k}]: {why}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="measure in-process and print one JSON line "
                    "(expects XLA_FLAGS host-device count already set)")
    args = ap.parse_args(argv)
    if args.json:
        res = _measure(_config(args.smoke))
        print(json.dumps(res))
        return 0
    res = run(smoke=args.smoke)
    print(report(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
