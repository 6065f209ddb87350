"""Readings a limit or a bound is set from: runs benchmark/run.py several
times in a row (each its own process; this parent never imports jax) and
keeps every result line under chiprun_out/.

  python3 benchmark/tests/readings.py <workload> <seconds> <spec> [<spec>...]

A spec is ``<variant>:<trace>:<seed>[,<seed>...]``; the variant ``run`` is the
program as the cell states it, ``control`` / ``unchanged`` / ``half_batch``
are run.py's test variants. Example: ``run:0:11,12 control:0:11 run:1:13``.
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    workload, seconds, *specs = sys.argv[1:]
    extra = []
    if "--dump_trace" in specs:
        specs.remove("--dump_trace")
        extra = ["--dump_trace"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"readings_{workload}.jsonl"), "a")
    for spec in specs:
        variant, trace, seeds = spec.rsplit(":", 2)
        for seed in seeds.split(","):
            cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                   "--workload", workload, "--seed", seed,
                   "--seconds", seconds, "--trace", trace, *extra]
            if variant != "run":
                cmd += ["--variant", variant]
            t0 = time.monotonic()
            p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                               stderr=subprocess.PIPE, text=True)
            wall = time.monotonic() - t0
            lines = p.stdout.strip().splitlines()
            rec = {"variant": variant, "trace": int(trace), "seed": int(seed),
                   "seconds": float(seconds), "rc": p.returncode,
                   "wall_s": round(wall, 1)}
            try:
                rec["line"] = json.loads(lines[-1])
            except (IndexError, ValueError):
                rec["stderr"] = p.stderr[-3000:]
            notes = [ln for ln in p.stderr.splitlines()
                     if ln.startswith("bench:")]
            rec["notes"] = notes[-14:]
            out.write(json.dumps(rec) + "\n")
            out.flush()
            line = rec.get("line", {})
            print(json.dumps({
                "variant": variant, "trace": int(trace), "seed": int(seed),
                "rc": p.returncode, "wall_s": rec["wall_s"],
                "correct": line.get("correct"),
                "metrics": {k: round(v["value"], 5) for k, v in
                            line.get("metrics", {}).items()},
                "compared": {k: float(f"{v['value']:.4g}") for k, v in
                             line.get("compared", {}).items()},
                "device": line.get("device"),
                "breakdown": line.get("breakdown")}), flush=True)
            if "stderr" in rec:
                print(rec["stderr"], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
