"""Record a small profiler trace on the accelerator and print its layout.

Runs two tiny jitted programs a few times under ``jax.profiler`` with host
spans around each call, then prints every plane and line of the recorded
``.xplane.pb`` with a few events each.  ``--out DIR`` also keeps the trace
file there (the trace-reduction test's fixture is recorded this way).

    python3 bench/tools/trace_probe.py --out traces/probe
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import tempfile
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _matmul_step(a, b):
        return jnp.tanh(a @ b)

    @jax.jit
    def _reduce_step(a):
        return jnp.sum(a * a, axis=0)

    a = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready((_matmul_step(a, a), _reduce_step(a)))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for i in range(3):
        with jax.profiler.TraceAnnotation("probe_step"):
            jax.block_until_ready(_matmul_step(a, a))
        with jax.profiler.TraceAnnotation("probe_idle"):
            time.sleep(0.01)
        with jax.profiler.TraceAnnotation("probe_reduce"):
            jax.block_until_ready(_reduce_step(a))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    print("trace", path, os.path.getsize(path), "bytes")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:4]:
                stats = {}
                try:
                    stats = {k: v for k, v in ev.stats}
                except Exception as e:          # noqa: BLE001
                    stats = {"error": repr(e)}
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={str(stats)[:300]}")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        shutil.copy(path, os.path.join(args.out, "probe.xplane.pb"))
    shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main()
