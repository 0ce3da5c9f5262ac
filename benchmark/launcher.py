#!/usr/bin/env python3
"""The server child of every benchmark run: `pilosa_tpu.cli`'s entry
point, unchanged, with one dormant thread beside it.

Only the process that holds the chip can trace it. So the parent
(run.py, which never imports jax) asks this process to: it drops a file
named `start` into the directory BENCH_LAUNCHER_CTL names, the thread
calls `jax.profiler.start_trace` and answers with `start.done`; `stop`
likewise. Runs with `--trace 0` start through this same file and never
drop those files, so both kinds of run serve through one path. The
profiler's Python tracer is switched off: with it every bytecode call of
the HTTP threads lands in the trace; host annotations
(`jax.profiler.TraceAnnotation`) stay on.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time


def _tracer(ctl_dir: str) -> None:
    def wait_for(name: str) -> None:
        path = os.path.join(ctl_dir, name)
        while not os.path.exists(path):
            time.sleep(0.05)

    def done(name: str) -> None:
        with open(os.path.join(ctl_dir, name + ".done"), "w"):
            pass

    wait_for("start")
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(
        os.path.join(ctl_dir, "trace"), profiler_options=options
    )
    done("start")
    wait_for("stop")
    jax.profiler.stop_trace()
    done("stop")


def main() -> int:
    ctl_dir = os.environ.get("BENCH_LAUNCHER_CTL")
    if ctl_dir:
        threading.Thread(
            target=_tracer, args=(ctl_dir,), name="bench-tracer", daemon=True
        ).start()
    # A parent started in the background of a non-interactive shell hands
    # down SIGINT ignored, and Python then installs no handler: the server's
    # clean shutdown (KeyboardInterrupt in cli.cmd_server) would never run.
    signal.signal(signal.SIGINT, signal.default_int_handler)
    from pilosa_tpu import cli

    return cli.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
