"""Shared on-chip sweep orchestration (conv_sweep.py / gpt_sweep.py).

One subprocess per config — one config's failure must not sink the
sweep, and the parent never touches a device, so each child gets the
chip — with full child stdout/stderr preserved per config (including
the killed-at-timeout case). Children print ONE JSON line; the parent appends
each record to ``--out`` as it lands, so a partial sweep still leaves a
readable artifact.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sweep(script: str, names: list[str], out: str, timeout: float,
              extra_child_args: list[str] | None = None) -> list[dict]:
    os.makedirs(os.path.dirname(out), exist_ok=True)
    results = []
    for name in names:
        cmd = [sys.executable, os.path.abspath(script), "--one", name]
        cmd += list(extra_child_args or [])
        t0 = time.time()
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        log_path = os.path.join(os.path.dirname(out), f"{name}.log")
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=timeout,
                cwd=REPO, env=env,
            )
            with open(log_path, "w") as lf:
                lf.write(proc.stdout)
                lf.write("\n--- stderr ---\n")
                lf.write(proc.stderr)
            line = (proc.stdout.strip().splitlines()[-1]
                    if proc.stdout.strip() else "")
            rec = json.loads(line) if line.startswith("{") else {
                "config": name, "error": (proc.stderr or "no output")[-400:],
                "rc": proc.returncode, "log": log_path,
            }
        except subprocess.TimeoutExpired as exc:
            with open(log_path, "w") as lf:
                for label, stream in (("stdout", exc.stdout),
                                      ("stderr", exc.stderr)):
                    lf.write(f"--- {label} (killed at timeout) ---\n")
                    if stream:
                        lf.write(stream if isinstance(stream, str)
                                 else stream.decode(errors="replace"))
                    lf.write("\n")
            rec = {"config": name, "log": log_path,
                   "error": f"timeout after {timeout:.0f}s"}
        except Exception as exc:  # noqa: BLE001 — record, keep sweeping
            rec = {"config": name, "error": f"{type(exc).__name__}: {exc}"}
        rec["wall_s"] = round(time.time() - t0, 1)
        results.append(rec)
        print(json.dumps(rec), flush=True)
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
    return results
