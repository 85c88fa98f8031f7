#!/usr/bin/env python
"""AOT warm-boot smoke — cold-process restart with and without the cache.

The shape-polymorphic AOT serving gate (docs/SERVING.md § AOT warm
boot): three FRESH child processes run the identical randomized-shape
replay (``serving/replay.py run_randomized_replay`` — prompt lengths
across the whole 1..max_prompt range, prefix cache + speculation armed):

  * **cold** — no ``DL4J_TPU_COMPILE_CACHE``: the plain jit path, every
    compiled fn paid for in-process;
  * **populate** — empty cache dir: every engine fn exports through
    ``jax.export`` into the persistent cache (``serving/aot.py``), and
    the leg runs the exported executables it just stored;
  * **warm** — the now-populated cache in another fresh process: every
    fn restores by deserialization.

Assertions (the acceptance criteria, not a vibe check):

  * the warm leg's ledger records ZERO serving ``first_compile`` events
    — every compiled fn it dispatched arrived as a ``cache_hit``;
  * outputs are **bit-identical** across all three legs (greedy replay,
    same seed — the exported artifact must reproduce the in-process jit
    token-for-token);
  * ZERO ``new_shape`` events on every leg — the symbolic/bucketed
    executables absorb the full shape diversity.

This is a CPU correctness gate, not a measurement: every leg runs with
``JAX_PLATFORMS=cpu`` (the parent pins it and never touches JAX itself —
a chip has one owner, and three restarting children cannot share it), and
the record carries no time. What a warm boot saves on the chip is not
measured here.

Contract (same as lint/check/obs/chaos/...): ONE JSON summary line on
stdout with ``"tool": "aot"``; exit 0 iff ``ok``. ``tools/gate.py``'s
``aot`` stage parses the line. ``--child`` runs a single leg in-process
(the mode the parent spawns). The exported artifacts live under a
throwaway directory (``--cache-dir`` keeps them); JAX's own persistent
cache follows the repo's one rule (``environment.enable_compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/`` in the
checkout).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ENV_DIR = "DL4J_TPU_COMPILE_CACHE"


def run_child_leg(requests: int, seed: int) -> dict:
    """One replay leg in THIS process (spawned via ``--child``). The
    parent controls the cache through the environment; the engine's
    constructor does the warm boot / export."""
    from deeplearning4j_tpu.serving.replay import run_randomized_replay

    out = run_randomized_replay(n_requests=requests, seed=seed)
    return {
        "outputs": out["outputs"],
        "first_compile_keys": out["first_compile_keys"],
        "cache_hit_keys": out["cache_hit_keys"],
        "new_shape_events": out["new_shape_events"],
        "all_terminal": bool(out["all_terminal"]),
    }


def spawn_leg(leg: str, cache_dir, requests: int, seed: int,
              timeout_s: float = 600.0) -> dict:
    """Run one leg in a FRESH python process — the restart the gate is
    about. Returns the child's JSON record."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(ENV_DIR, None)
    if cache_dir is not None:
        env[ENV_DIR] = cache_dir
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", leg,
         "--requests", str(requests), "--seed", str(seed)],
        capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=REPO)
    for ln in proc.stdout.splitlines():
        ln = ln.strip()
        if ln.startswith("{") and '"leg"' in ln:
            return json.loads(ln)
    raise RuntimeError(
        f"{leg} leg emitted no record (rc={proc.returncode}): "
        f"{proc.stderr[-800:]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="machine-readable: exactly one JSON line on stdout")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--cache-dir", default=None,
                    help="reuse (and keep) this cache dir instead of a "
                         "throwaway tempdir")
    ap.add_argument("--child", default=None, metavar="LEG",
                    help=argparse.SUPPRESS)  # internal: run one leg inline
    args = ap.parse_args()

    if args.child:
        rec = run_child_leg(args.requests, args.seed)
        rec["leg"] = args.child
        print(json.dumps(rec), flush=True)
        return 0

    tmp = None
    cache_dir = args.cache_dir
    if cache_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="dl4j_tpu_aot_")
        cache_dir = tmp.name
    try:
        cold = spawn_leg("cold", None, args.requests, args.seed)
        populate = spawn_leg("populate", cache_dir, args.requests, args.seed)
        warm = spawn_leg("warm", cache_dir, args.requests, args.seed)
    finally:
        if tmp is not None:
            tmp.cleanup()

    identical = (cold["outputs"] == warm["outputs"]
                 and cold["outputs"] == populate["outputs"])
    warm_first_compiles = warm["first_compile_keys"]
    new_shape = (cold["new_shape_events"] + populate["new_shape_events"]
                 + warm["new_shape_events"])
    all_terminal = all(r["all_terminal"] for r in (cold, populate, warm))

    ok = (warm_first_compiles == []
          and len(warm["cache_hit_keys"]) > 0
          and identical
          and all_terminal
          and new_shape == 0)

    rec = {
        "tool": "aot", "ok": ok,
        "warm_first_compile_keys": warm_first_compiles,
        "warm_cache_hit_keys": warm["cache_hit_keys"],
        "outputs_identical": identical,
        "all_terminal": all_terminal,
        "new_shape_events": new_shape,
        "cold_first_compile_keys": cold["first_compile_keys"],
        "requests_per_leg": args.requests,
    }
    print(json.dumps(rec), flush=True)
    if not args.json:
        print(f"aot: {'OK' if ok else 'FAIL'} — warm first_compiles="
              f"{warm_first_compiles}, cache_hits={warm['cache_hit_keys']}, "
              f"identical={identical}, new_shape={new_shape}",
              file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
