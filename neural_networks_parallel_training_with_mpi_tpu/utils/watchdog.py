"""Step-hang watchdog (failure detection, SURVEY.md §5.3).

The reference has no failure handling: a lost rank hangs
``comm.gather`` forever (dataParallelTraining_NN_MPI.py:185) and the job
blocks silently until the scheduler kills it.  The TPU-native equivalents of
that failure mode — a peer host dropping out of a DCN collective, a wedged
device — stall inside ``block_until_ready`` the same way.

:class:`HangWatchdog` converts the silent stall into a loud, diagnosable
failure: a daemon thread tracks a heartbeat the train loop pats every step,
and if no progress happens within ``timeout_s`` it dumps the stack of every
thread to stderr and hard-exits the process (a stuck XLA collective cannot
be interrupted from Python, so graceful unwinding is not an option — the
point is that *this* host fails fast with a diagnosis instead of hanging the
whole job).  Enabled via ``--hang_timeout`` seconds.
"""

from __future__ import annotations

import contextlib
import faulthandler
import os
import sys
import threading
import time
from typing import Optional


class HangWatchdog:
    """``with HangWatchdog(120):`` + ``wd.pat()`` once per step.

    The clock only arms at the FIRST ``pat()``: the first step includes XLA
    compilation (tens of seconds for big programs), which must not count as
    a hang.  Known-long host-side phases (eval passes, checkpoint writes)
    should run inside ``with wd.suspended():`` — the check pauses and the
    clock resets when the phase ends.  What's protected is therefore the
    steady-state step loop, which is exactly where a lost peer stalls.
    """

    def __init__(self, timeout_s: Optional[float], what: str = "train step",
                 _exit=os._exit, on_timeout=None):
        self.timeout_s = timeout_s
        self.what = what
        self._exit = _exit  # injectable for tests
        # best-effort last act before the hard exit (the Trainer hooks the
        # telemetry flight-recorder dump here); must never block the exit
        self.on_timeout = on_timeout
        self._beat: Optional[float] = None  # None until armed by first pat
        self._suspended = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def pat(self) -> None:
        self._beat = time.monotonic()

    @contextlib.contextmanager
    def suspended(self):
        """Pause hang detection for a known-long non-step phase."""
        self._suspended += 1
        try:
            yield
        finally:
            # reset the heartbeat BEFORE un-suspending: the watchdog thread
            # must never observe _suspended==0 with a beat that is stale
            # from before the suspended phase
            if self._beat is not None:
                self.pat()
            self._suspended -= 1

    def _run(self) -> None:
        assert self.timeout_s is not None
        poll = min(self.timeout_s / 4.0, 5.0)
        while not self._stop.wait(poll):
            if self._beat is None or self._suspended:
                continue
            idle = time.monotonic() - self._beat
            if idle > self.timeout_s:
                print(
                    f"HANG DETECTED: no {self.what} progress for "
                    f"{idle:.0f}s (> {self.timeout_s:.0f}s). Dumping all "
                    "thread stacks and aborting this process — a stuck XLA "
                    "collective cannot be interrupted from Python. The "
                    "reference's equivalent failure hangs forever in "
                    "comm.gather.", file=sys.stderr, flush=True)
                try:  # needs a real fd; stderr may be captured/redirected
                    faulthandler.dump_traceback(file=sys.stderr)
                    sys.stderr.flush()
                except Exception:
                    pass
                if self.on_timeout is not None:
                    try:
                        self.on_timeout()
                    except Exception:
                        pass  # the dump is best-effort; exit regardless
                self._exit(42)
                return  # only reached with an injected _exit (tests)

    def __enter__(self) -> "HangWatchdog":
        if self.timeout_s and self.timeout_s > 0:
            self._thread = threading.Thread(
                target=self._run, name="hang-watchdog", daemon=True)
            self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
