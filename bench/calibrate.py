"""Readings that set the limits of the comparison with the reference.

    python3 bench/calibrate.py --workload <cell> --first-seed <n> \
        --seeds 12 --control-seeds 3 --seconds <s> [--out <file.jsonl>]

Sets the cell up once, then for each seed runs the window exactly as
``bench/run.py`` does and compares every answer with the reference: the
program's readings, whose largest is the lower end of each limit.  For the
first ``--control-seeds`` seeds it also puts the control in the program's
place and compares its answers the same way: the upper end.

The control is the program kind's own (``control`` in
``bench/programs/<program>.py``): the reference computed in the precision
below the program's, or, where the program states none, with one of its
guarantees broken.

Each reading is one JSON line; the benchmark's own runs never call this.
"""

import argparse
import json
import os
import sys
import time


def control_batches(cell, setup, batches):
    """The window's batches with the control's answers in place."""
    from bench import harness as h

    return [h.Batch(b.keys, dist, b.n_supersteps, b.inner_iters,
                    h.reached_edges(setup.degree, dist), b.seconds)
            for b, dist in zip(batches, cell.kind.control(cell, setup, batches))]


def main(argv=None) -> int:
    t_start = time.perf_counter()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    ap = argparse.ArgumentParser(description="Readings for the limits of one cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import harness as h

    cell = h.load_cell(args.workload)
    setup = h.set_up(cell, devices=h.accelerators(cell.chips))
    pool = h.key_pool(cell, setup.degree)
    out = open(args.out, "a") if args.out else sys.stdout
    try:
        for i in range(args.seeds):
            seed = args.first_seed + i
            done, window_s = h.run_window(setup, pool, seed, args.seconds,
                                          h.no_annotation)
            values, failed = h.compare(cell, setup, done)
            ctx = h.Context(n=setup.n, n_edges=setup.src.size // 2, batches=done,
                            window_s=window_s, setup_s=0.0, setup_seconds={},
                            relax_bytes=0)
            e2e = h.read_metrics(cell.end_to_end, ctx, h.ROOT)
            rec = {"workload": cell.name, "seed": seed, "side": "program",
                   "batches": len(done), "window_s": window_s,
                   "e2e": {k: v["value"] for k, v in e2e.items() if k != "setup_s"},
                   "failed": failed, "values": values}
            print(json.dumps(rec), file=out, flush=True)
            if i < args.control_seeds:
                values, failed = h.compare(cell, setup, control_batches(cell, setup, done))
                rec.update(side="control", failed=failed, values=values, e2e={})
                print(json.dumps(rec), file=out, flush=True)
    finally:
        if out is not sys.stdout:
            out.close()
    print(f"calibrate: {time.perf_counter() - t_start:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
