"""The share of the device's idle time, inside the traced window, that at
least one of the program's own spans covers, in percent. The program
mirrors a closed set of its tracer's spans into the profiler's trace and
names them on ``/healthz`` (``trace.mirrored_spans``, read at ``at``);
``benchmark/harness/xspans.py`` finds them in the trace file, in a process
of its own. Returns nothing without a trace of a device, without that list
(a program from before it wrote one), or where the device was never idle."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(ctx: dict, at: str = "open"):
    trace = ctx.get("trace")
    if not trace or trace.get("device_stand_in") or not os.path.exists(trace.get("file") or ""):
        return None
    names = ((ctx["health"].get(at) or {}).get("trace") or {}).get("mirrored_spans")
    if not names:
        return None
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.xspans", trace["file"], *names], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    if done.returncode != 0:
        print(f"span reduction failed: {done.stderr[-400:]}", file=sys.stderr)
        return None
    share = json.loads(done.stdout.strip().splitlines()[-1])["covered_share"]
    return None if share is None else 100.0 * share
