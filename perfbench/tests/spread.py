"""Run one cell several times, one process per run, and print each metric's
spread as the builder's instructions define it: the distance between the
first and third quartile (`statistics.quantiles(values, n=4)`) as a share of
the median, per set of runs with the same seeds in every set.

    python perfbench/tests/spread.py --workload W --seconds S \
        --seeds 11,22,33,44,55,66 --sets 2 [--trace 0] [--out chiprun_out/x.jsonl]

This parent never touches JAX (a chip belongs to one process at a time).
Every run's result line is appended to ``--out`` with its seed, set, wall
seconds and exit code; the child's log goes to ``<out>.log``.
"""

import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cwd", default=str(ROOT),
                    help="run from this checkout (e.g. an unpacked archive)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = pathlib.Path(args.out) if args.out else None
    if out:
        out.parent.mkdir(parents=True, exist_ok=True)
    sets = []
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            cmd = [sys.executable, "perfbench/run.py", "--workload",
                   args.workload, "--seed", str(seed), "--seconds",
                   str(args.seconds), "--trace", str(args.trace)]
            t = time.time()
            p = subprocess.run(cmd, cwd=args.cwd, capture_output=True,
                               text=True)
            wall = time.time() - t
            lines = p.stdout.strip().splitlines()
            row = {"set": k, "seed": seed, "rc": p.returncode,
                   "wall_s": round(wall, 1)}
            if p.returncode == 0 and lines:
                row.update(json.loads(lines[-1]))
            else:
                print(p.stdout[-3000:], p.stderr[-3000:], sep="\n")
            rows.append(row)
            if out:
                with open(out, "a") as f:
                    f.write(json.dumps(row) + "\n")
                with open(str(out) + ".log", "a") as f:
                    f.write(f"==== set {k} seed {seed} rc {p.returncode}\n"
                            + p.stdout + p.stderr[-4000:])
            m = {n: v["value"] for n, v in row.get("metrics", {}).items()}
            print(f"set {k} seed {seed} rc {p.returncode} wall {wall:.0f}s "
                  f"correct {row.get('correct')} " + " ".join(
                      f"{n}={v:.6g}" for n, v in m.items()), flush=True)
        sets.append(rows)
    names = sorted({n for rows in sets for r in rows
                    for n in r.get("metrics", {})})
    print(f"\n{args.workload}: {args.sets} set(s) of {len(seeds)} run(s), "
          f"{args.seconds:g} s each")
    for n in names:
        cols = []
        for rows in sets:
            vals = [r["metrics"][n]["value"] for r in rows
                    if n in r.get("metrics", {})]
            if len(vals) >= 2:
                cols.append((statistics.median(vals), spread(vals)))
        print(f"  {n:28s} " + "  ".join(
            f"median {m:.6g} spread {100 * s:.3f}%" for m, s in cols)
            + (f"  widest {100 * max(s for _, s in cols):.3f}%"
               if cols else ""))
    return 0 if all(r["rc"] == 0 for rows in sets for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
