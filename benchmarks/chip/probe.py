#!/usr/bin/env python3
"""Readings that the correctness limits are set from (not part of a run).

    python3 benchmarks/chip/probe.py --workload <cell> --seeds 1 2 3 \\
        --side program|fp8|half_batch

For each seed, one line of JSON with the numbers `oracle.compare` gives:
  program     the program, driven through the harness's set-up exactly as
              a run drives it, against the float32 reference (the lower
              readings);
  fp8         the control: the reference computed with float8_e4m3fn
              matrix-product operands, the precision below the
              configuration's bfloat16, put in the program's place;
  half_batch  the fault "half of the batch left out, the mean taken over
              the rest", planted in the reference put in the program's
              place.
All seeds run in one process, one after the other; each frees what the
last one held.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import oracle  # noqa: E402


def readings(cell: dict, seed: int, side: str, *, require_tpu: bool = True):
    """-> {number: value} for one seed."""
    import jax
    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("probe needs a TPU")
    if side == "program":
        trainer, held, prog = harness.set_up(cell, seed)
        del trainer, held
        gc.collect()
    else:
        prog = harness.reference_readings(cell, seed, variant=side)
    ref = harness.reference_readings(cell, seed)
    nums = oracle.compare(prog, ref)
    return {k: nums[k][0] for k in oracle.NUMBERS} | {
        f"{k}_leaf": nums[k][1] for k in oracle.NUMBERS} | {
        "program_loss": prog["loss"], "reference_loss": ref["loss"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--side", choices=("program", "fp8", "half_batch"),
                    required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    from repro.launch.cache import configure_compile_cache
    import jax
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in args.seeds:
        out = readings(cell, seed, args.side)
        print(json.dumps({"workload": args.workload, "side": args.side,
                          "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
