#!/usr/bin/env python3
"""Readings for the limits of the train step's check, on the chip.

    python3 perf/readings.py --config <name> --chips <n> --seeds 12 [--rehearsal]

For each seed it prints the four numbers the benchmark compares (widest
loss gap, worst-leaf gap of the first gradient's norm, worst-leaf gap of
the norm of the parameters' change, worst-leaf norm of the first
gradient's difference) twice: the program against the plain
reference (a sound run), and each control, the reference with its linear
layers rounded to 8 bits (int8, fp8), against the same reference. PERF.md gives the
readings each limit was set from. The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--chips", type=int, default=1)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2_200_000_000)
    parser.add_argument("--controls", default="int8,fp8")
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    from perf import harness
    from tpusnap import compile_cache

    compile_cache.enable()
    devices = harness.find_devices(args.chips, args.rehearsal)
    if devices is None:
        return 2
    config = harness.read_json("configs", f"{args.config}.json")
    if args.rehearsal:
        config = harness.read_json("configs", f"{config['rehearsal_config']}.json")

    gaps = harness.first_step_gaps

    rows, controls = [], [c for c in args.controls.split(",") if c]
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        tokens = harness.first_tokens(config, seed)
        key = harness.seed_key(seed)
        want = harness.reference_first_steps(config, key, tokens, devices)
        row = {"seed": seed}
        for quant in controls:
            control = harness.reference_first_steps(config, key, tokens, devices, quant=quant)
            row[f"control_{quant}"] = gaps(control, want)
        ctx = harness.build_program(config, devices, seed)
        row["sound"] = gaps(harness.program_first_steps(ctx, tokens), want)
        del ctx
        rows.append(row)
        harness.say("reading", platform=devices[0].platform, **row)
    for name in rows[0]["sound"]:
        harness.say(
            "summary", number=name, platform=devices[0].platform, seeds=len(rows),
            sound_largest=max(r["sound"][name] for r in rows),
            **{f"control_{q}_smallest": min(r[f"control_{q}"][name] for r in rows)
               for q in controls},
        )
    print(json.dumps({"readings": len(rows), "platform": devices[0].platform}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
