"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig10,table2] [--fast]
                                          [--smoke] [--compare]

Writes results/bench/<name>.json + a combined markdown report, prints
``name,seconds,headline`` CSV lines, and emits one repo-root
``BENCH_<name>.json`` artifact per benchmark (schema: ``{name, config,
metrics, timestamp, git_sha}``) so the perf trajectory is recorded and
CI can upload it.  --fast skips the QAT-training-heavy tables unless
their caches exist (CI mode); --smoke asks each benchmark that supports
it for a reduced-size run (shared-runner mode).

--compare gates the perf trajectory: before overwriting a repo-root
artifact, the committed baseline is loaded and every metric the bench
declares in its ``THROUGHPUT_METRICS`` dict ({dotted.path: "lower" |
"higher"}) is diffed — a >20% regression in the throughput direction
fails the run (exit 2).  Benches should gate host-invariant ratios
(e.g. fused-vs-pallas speedup) and list noise-prone absolute numbers in
``INFO_METRICS`` instead, whose deltas are printed but never gate.
Benches may also declare ``SPEED_CHECKS``: names of boolean
``res["checks"]`` entries (intra-run ratios, robust to host noise) that
must hold under --compare.  Baselines recorded with a different config
(e.g. a --smoke run vs a committed full-size artifact) are skipped with
a note instead of producing bogus deltas.
"""

from __future__ import annotations

import argparse
import datetime
import inspect
import json
import os
import subprocess
import time
import traceback

import jax

from benchmarks import (backend_parity, compiler_report, fault_injection,
                        fig6_channels, fig10_switching, fig11_energy,
                        llm_serving, roofline_report, serving_load,
                        sharding_scaling, spec_decode, table2_tiling,
                        table4_strategies, table5_sota)

HEAVY = {"table4", "fig11", "compiler"}

BENCHES = {
    "table2": table2_tiling,
    "table4": table4_strategies,
    "fig6": fig6_channels,
    "fig10": fig10_switching,
    "fig11": fig11_energy,
    "table5": table5_sota,
    "roofline": roofline_report,
    "backends": backend_parity,
    "compiler": compiler_report,
    "serving": serving_load,
    "sharding": sharding_scaling,
    "llm_serving": llm_serving,
    "spec_decode": spec_decode,
    "faults": fault_injection,
}

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache where
    JAX_COMPILATION_CACHE_DIR says (JAX reads the variable itself), or
    else at one fixed path in the checkout, so later runs hit it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO_ROOT, ".jax_cache"))


def _headline(name: str, res: dict) -> str:
    if "checks" in res:
        # None = recorded but not evaluated (e.g. speed checks on hosts
        # without enough cores); only true/false checks count.
        evaluated = {k: v for k, v in res["checks"].items()
                     if v is not None}
        ok = sum(bool(v) for v in evaluated.values())
        return f"{ok}/{len(evaluated)} checks pass"
    if name == "roofline":
        return f"{res['n_cells']} cells"
    return "ok"


def _git_sha() -> str:
    sha = os.environ.get("GITHUB_SHA")
    if sha:
        return sha
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO_ROOT, text=True,
            capture_output=True, timeout=10).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — no git in the environment
        return "unknown"


def write_artifact(name: str, res: dict, git_sha: str) -> str:
    """Repo-root BENCH_<name>.json: the recorded perf-trajectory point."""
    artifact = {
        "name": name,
        "config": res.get("config", {}),
        "metrics": {k: v for k, v in res.items() if k != "config"},
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "git_sha": git_sha,
    }
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1, default=str)
    return path


def _call_run(mod, smoke: bool) -> dict:
    """mod.run(), passing smoke= through to benchmarks that take it."""
    if smoke and "smoke" in inspect.signature(mod.run).parameters:
        return mod.run(smoke=True)
    return mod.run()


# ---------------------------------------------------------------------------
# --compare: perf-trajectory gate against the committed artifacts
# ---------------------------------------------------------------------------

REGRESSION_THRESHOLD = 0.20


def _load_baseline(name: str):
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _metric_at(metrics: dict, path: str):
    cur = metrics
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) else None


def compare_artifact(mod, name: str, old, res: dict
                     ) -> tuple[list[str], list[str]]:
    """Diff a fresh result against the committed baseline artifact.

    Returns (report lines, regression descriptions).  Intra-run
    ``SPEED_CHECKS`` are enforced unconditionally; per-metric deltas are
    only meaningful against a baseline recorded with the same config.
    """
    lines, regressions = [], []
    for key in getattr(mod, "SPEED_CHECKS", ()):
        ok = res.get("checks", {}).get(key)
        lines.append(f"  {name}: speed check {key} = {ok}")
        if ok is False:
            regressions.append(f"{name}: speed check {key} failed")
    gated = getattr(mod, "THROUGHPUT_METRICS", {})
    info = getattr(mod, "INFO_METRICS", {})
    if not gated and not info:
        return lines, regressions
    if old is None:
        lines.append(f"  {name}: no committed BENCH_{name}.json baseline; "
                     "skipping metric diff")
        return lines, regressions
    new_config = res.get("config", {})
    if old.get("config", {}) != new_config:
        lines.append(f"  {name}: baseline config {old.get('config', {})} "
                     f"!= {new_config}; skipping metric diff")
        return lines, regressions
    for path, direction in {**info, **gated}.items():
        a = _metric_at(old.get("metrics", {}), path)
        b = _metric_at({k: v for k, v in res.items() if k != "config"},
                       path)
        if a is None or b is None or a == 0:
            lines.append(f"  {name}.{path}: not comparable "
                         f"({a!r} -> {b!r})")
            continue
        delta = (b - a) / abs(a)
        worse = delta > 0 if direction == "lower" else delta < 0
        bad = path in gated and worse and abs(delta) > REGRESSION_THRESHOLD
        lines.append(f"  {name}.{path}: {a:.4g} -> {b:.4g} ({delta:+.1%})"
                     + ("  REGRESSION" if bad else ""))
        if bad:
            regressions.append(
                f"{name}.{path}: {a:.4g} -> {b:.4g} ({delta:+.1%}, "
                f"{direction} is better)")
    return lines, regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true",
                    help="skip QAT-heavy benches without a cache")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-size runs where supported (CI smoke)")
    ap.add_argument("--compare", action="store_true",
                    help="diff fresh artifacts against the committed "
                         "BENCH_<name>.json; >20%% throughput regression "
                         "or a failed speed check exits non-zero")
    ap.add_argument("--out", default="results/bench")
    args = ap.parse_args(argv)

    use_compile_cache()
    names = (args.only.split(",") if args.only else list(BENCHES))
    os.makedirs(args.out, exist_ok=True)
    git_sha = _git_sha()
    report_md, failures = [], []
    compare_lines, regressions = [], []
    print("name,seconds,headline")
    for name in names:
        mod = BENCHES[name]
        if args.fast and name in HEAVY:
            cache = getattr(mod, "CACHE", None)
            if not (cache and os.path.exists(cache)):
                print(f"{name},0.0,skipped (--fast; no cache)")
                continue
        baseline = _load_baseline(name) if args.compare else None
        t0 = time.time()
        try:
            res = _call_run(mod, args.smoke)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            traceback.print_exc()
            print(f"{name},{time.time() - t0:.1f},FAILED {e!r}")
            continue
        dt = time.time() - t0
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump(res, f, indent=1, default=str)
        write_artifact(name, res, git_sha)
        report_md.append(mod.report(res))
        print(f"{name},{dt:.1f},{_headline(name, res)}")
        if args.compare:
            lines, regs = compare_artifact(mod, name, baseline, res)
            compare_lines += lines
            regressions += regs

    with open(os.path.join(args.out, "REPORT.md"), "w") as f:
        f.write("\n\n".join(report_md) + "\n")
    if args.compare and compare_lines:
        print("perf trajectory vs committed artifacts:")
        print("\n".join(compare_lines))
    if failures:
        print(f"{len(failures)} benchmark(s) failed: {failures}")
        return 1
    if regressions:
        print(f"{len(regressions)} throughput regression(s): {regressions}")
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
