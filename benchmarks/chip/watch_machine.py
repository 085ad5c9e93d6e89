"""Tells a stall of the machine from a stall of the benchmark's process.

    python3 benchmarks/chip/watch_machine.py <log> &
    python3 benchmarks/chip/run.py --workload <cell> ... ; kill %1

A process of its own that touches neither JAX nor the chip: it sleeps
``BEAT_S`` at a time and writes every silence of its own over ``GAP_S``,
on the wall clock.  Where a run's longest step (``step_max_s`` at
``step_max_at_s`` in its notes, with the heartbeat's silence beside it)
falls into such a line, everything on the machine stood still, and the
program was not at fault; where this log is silent meanwhile, the
benchmark's process alone was held up.  PERF.md section 6, PR 31 g, has
what it found.  Not part of a run: ``run.py`` never starts it.
"""

import sys
import time

BEAT_S = 0.05
GAP_S = 0.3


def watch(path, seconds=None, beat_s=BEAT_S, gap_s=GAP_S):
    """Write to ``path`` until killed, or for ``seconds``."""
    with open(path, "a") as out:
        t0 = last = time.monotonic()
        print(f"start wall {time.time():.3f}", file=out, flush=True)
        while seconds is None or last - t0 < seconds:
            time.sleep(beat_s)
            now = time.monotonic()
            if now - last > gap_s:
                print(f"silent {now - last:.3f} s until +{now - t0:.1f} s, "
                      f"wall {time.time():.3f}", file=out, flush=True)
            last = now


if __name__ == "__main__":
    watch(sys.argv[1])
