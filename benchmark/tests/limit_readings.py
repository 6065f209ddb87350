"""Readings a cell's limits are set from, one process a seed.

  python3 benchmark/tests/limit_readings.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1> [--variant half_batch|unchanged]

Where a run's check takes minutes (``joyai_flash_sketch_1c``: one follow of
the reference at d = 414M is four of them on the chip), ``run.py --variant
control`` and a plain run of the same seed pay set-up and the float32
reference twice.
This runs the cell once, as ``harness.main`` does and with its code (the same
``Run``, entry point, window, ``check`` and result line), keeps the float32
reference that ``check`` followed, and compares with it, beside the cell's
limits:

- ``program``: the timed program (under ``--variant``'s planted fault, if
  one is given), which is the run's own verdict;
- ``control``: the reference with the cell's 8-bit matmul operands in the
  program's place (what ``run.py --variant control`` compares);
- ``half_batch_ref``: the reference itself on the rounds' batches with the
  second half of the clients left out (``harness._drop_half``): what the
  planted fault does to each number, without a second run of the program
  (``run.py --variant half_batch`` is the fault under the program).

The last line of standard output is the run's result line with these under
``readings``; every line is also kept in
``chiprun_out/limit_readings_<cell>.jsonl``, the round events beside it. The
reference's programs are not written to the persistent compile cache, so the
round's stay in it for the next seed. A reading, never a measurement of the
benchmark: the driver runs ``run.py``.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--variant", default="",
                    choices=("", "unchanged", "half_batch"))
    ap.add_argument("--rehearse", action="store_true")
    ns = ap.parse_args()
    ns.dump_trace = False
    sys.path.insert(0, BENCH)
    import harness

    run = harness.Run(ns, T0)
    run.environment()
    run.reference_model()
    entry = harness.Entry(run.config["entry"])
    entry.install(run)
    run.schedule_kind = entry.schedule_kind

    import jax
    import reference

    followed = []            # (arguments, result) of every reference.follow
    follow = reference.follow

    def remembered(*args):
        out = follow(*args)
        followed.append((args, out))
        return out

    reference.follow = remembered
    limits = run.extras["limits"]

    def numbers(verdict):
        out = {n: e["value"] for n, e in verdict["compared"].items()}
        out.update(verdict["not_compared"])
        return {"correct": verdict["correct"], "numbers": out}

    real_stdout, sys.stdout = sys.stdout, sys.stderr
    try:
        entry.main(run.argv())
        run.grad_size = int(run.model.grad_size)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
        verdict = run.check()
        line = run.metrics(verdict)
        (model, traffic, seed, batches, spe, block, _), ref = followed[0]
        readings = {"program": numbers(verdict)}
        t = time.monotonic()
        control = follow(model, traffic, seed, batches, spe, block,
                         run.extras["control"])
        readings["control"] = numbers(reference.compare(control, ref, limits))
        harness.log(f"control {run.extras['control']}: "
                    f"{time.monotonic() - t:.1f} s")
        t = time.monotonic()
        half = follow(model, traffic, seed,
                      [harness._drop_half(b) for b in batches], spe, block,
                      None)
        readings["half_batch_ref"] = numbers(
            reference.compare(half, ref, limits))
        harness.log(f"half_batch_ref: {time.monotonic() - t:.1f} s")
    finally:
        sys.stdout = real_stdout
    line.update(readings=readings, seed=ns.seed, variant=ns.variant,
                trace=ns.trace, check_s=run.check_s,
                window={k: run.window[k] for k in ("rounds", "seconds")})
    for who, r in readings.items():
        for name, v in r["numbers"].items():
            harness.log(f"{who} {name}: {v:.6g}"
                        + (f" (limit {limits[name]:g})" if name in limits
                           else " (not compared)"))
        harness.log(f"{who}: correct={r['correct']}")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"limit_readings_{ns.workload}.jsonl"),
              "a") as f:
        f.write(json.dumps(line) + "\n")
    events = os.path.join(run.run_dir, "telemetry.jsonl")
    if os.path.exists(events):
        shutil.copy(events, os.path.join(
            out_dir, f"events_{ns.workload}_{ns.seed}{ns.variant}.jsonl"))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
