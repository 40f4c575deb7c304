"""Deadline watchdog: hangs become first-class, classified faults.

The failure mode the crash/NaN ladder (stepguard) cannot see is a run
that simply *stops making progress* — a wedged device, a
backend init that never returns, a compile that spins.  Every driver
does exactly one blocking host fetch per fused window, so "hung" has a
precise, observable definition: that fetch exceeded its wall-clock
budget.  A :class:`Watchdog` arms a monitor thread around the fetch;
on expiry it

  1. emits a structured ``hang`` telemetry event,
  2. writes an emergency manifest-valid ``hang_NNNNN/`` dump from the
     last *fetched host* state (never touching the device — the device
     is what hung),
  3. raises :class:`HangDetected` in the main thread (a SIGALRM-based
     soft interrupt, which breaks out of injected hangs and most
     interruptible waits), and
  4. if the guarded section still has not exited after a grace period
     (a true uninterruptible hang in C), hard-exits the process with
     :data:`HANG_EXIT_CODE` so a parent supervisor — the serve loop's
     stale reclaim, bench.py's subprocess parent, a cluster batch
     system — can classify hang vs crash by exit status.

``resilience/supervisor.py`` catches :class:`HangDetected` distinctly
from crashes and NaN ladders and applies the hang policy: immediate
resume from the newest checkpoint (no backoff, no dt-halving — the
state is not numerically suspect) under a bounded hang-retry budget.

Deadlines come from ``&RUN_PARAMS`` / ``&ENSEMBLE_PARAMS``
(``compile_deadline_s`` / ``step_deadline_s`` / ``io_deadline_s``) or
the matching ``RAMSES_*_DEADLINE_S`` environment overrides.  All three
unset means :meth:`Watchdog.from_params` returns ``None`` — the same
zero-overhead off switch as StepGuard/FaultInjector: drivers skip the
guard entirely and add no host<->device fetches (pinned by the
device_get-counting test in ``tests/test_watchdog.py``).
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Optional

#: process exit status for an unrecoverable (hard) hang — distinct
#: from crash (nonzero) and clean exit so parents classify by rc.
HANG_EXIT_CODE = 87

PHASES = ("compile", "step", "io")

_lock = threading.Lock()
_pending: Dict[str, Any] = {}      # monitor -> main-thread handoff
_installed = False
_prev_handler = None


class HangDetected(RuntimeError):
    """A guarded phase exceeded its wall-clock deadline.

    Carries the classification payload (phase, deadline, last-known
    host step/time) so supervisors can log hang-vs-crash distinctly.
    """

    def __init__(self, phase: str = "step", deadline_s: float = 0.0,
                 nstep=None, t=None):
        self.phase = phase
        self.deadline_s = float(deadline_s)
        self.nstep = nstep
        self.t = t
        at = f" at nstep={nstep}" if nstep is not None else ""
        super().__init__(f"phase {phase!r} exceeded "
                         f"{self.deadline_s:g}s deadline{at}")


def _on_alarm(signum, frame):
    """SIGALRM entry: raise the pending hang in the main thread.  With
    nothing pending (foreign alarm) defer to the previous handler."""
    with _lock:
        info = _pending.pop("hang", None)
    if info is None:
        prev = _prev_handler
        if callable(prev):
            prev(signum, frame)
        return
    raise HangDetected(**info)


def _install_handler() -> bool:
    """Install the shared SIGALRM soft-interrupt handler (idempotent;
    main thread only — elsewhere the hard-exit path still covers)."""
    global _installed, _prev_handler
    if _installed:
        return True
    if threading.current_thread() is not threading.main_thread():
        return False
    try:
        prev = signal.signal(signal.SIGALRM, _on_alarm)
    except (ValueError, OSError):      # no signals on this platform
        return False
    if prev not in (signal.SIG_DFL, signal.SIG_IGN, None):
        _prev_handler = prev
    _installed = True
    return True


def _uninstall_handler():
    """Restore the pre-watchdog SIGALRM disposition (test hygiene)."""
    global _installed, _prev_handler
    if not _installed:
        return
    try:
        signal.signal(signal.SIGALRM, _prev_handler or signal.SIG_DFL)
    except (ValueError, OSError):
        pass
    _installed = False
    _prev_handler = None
    with _lock:
        _pending.clear()


class Watchdog:
    """Per-phase wall-clock deadlines around blocking device fetches.

    Drivers hold ``self._wd = Watchdog.from_params(params)`` — ``None``
    when every deadline is unset (zero-overhead off) — and wrap each
    fused-window dispatch+fetch in ``with wd.guard("step"): ...``.
    The first step guard per process uses ``compile_deadline_s`` when
    set (compile happens inside the first dispatch), later ones
    ``step_deadline_s``; dump paths use ``guard("io")``.

    After every successful fetch the driver calls
    ``wd.note(nstep=..., t=...)`` so the expiry path can stamp the
    emergency dump and telemetry with the last *fetched* host state.
    """

    def __init__(self, compile_deadline_s: float = 0.0,
                 step_deadline_s: float = 0.0,
                 io_deadline_s: float = 0.0,
                 telemetry=None, base_dir: str = ".",
                 grace_s: float = 30.0, hard_exit: bool = True):
        self.deadlines = {"compile": float(compile_deadline_s or 0.0),
                          "step": float(step_deadline_s or 0.0),
                          "io": float(io_deadline_s or 0.0)}
        self.telemetry = telemetry
        self.base_dir = str(base_dir or ".")
        self.grace_s = float(os.environ.get("RAMSES_HANG_GRACE_S",
                                            grace_s))
        self.hard_exit = bool(hard_exit)
        self.hangs = 0                 # expiries observed
        self._warmed = False           # first step guard == compile
        self._host: Dict[str, Any] = {}
        self._ndump = 0
        self._installed = _install_handler()

    # ---- construction -------------------------------------------------

    @classmethod
    def from_params(cls, params, scope: str = "run", telemetry=None,
                    base_dir: Optional[str] = None
                    ) -> Optional["Watchdog"]:
        """A watchdog when any ``*_deadline_s`` is set under the
        ``scope`` group (``run`` or ``ensemble``) or the matching
        ``RAMSES_{COMPILE,STEP,IO}_DEADLINE_S`` env override, else
        ``None`` (the zero-overhead off switch)."""
        grp = getattr(params, scope, None)

        def pick(key: str) -> float:
            env = os.environ.get(f"RAMSES_{key.upper()}")
            if env is not None:
                try:
                    return float(env)
                except ValueError:
                    pass
            return float(getattr(grp, key, 0.0) or 0.0)

        c = pick("compile_deadline_s")
        s = pick("step_deadline_s")
        io = pick("io_deadline_s")
        if c <= 0.0 and s <= 0.0 and io <= 0.0:
            return None
        if base_dir is None:
            base_dir = str(getattr(getattr(params, "output", None),
                                   "output_dir", "."))
        return cls(c, s, io, telemetry=telemetry, base_dir=base_dir)

    # ---- host-state bookkeeping --------------------------------------

    def note(self, **fields):
        """Record the latest fetched host scalars (nstep, t, ...) —
        the only state the expiry path may touch."""
        self._host.update(fields)

    # ---- guarding -----------------------------------------------------

    def _effective(self, phase: str):
        """(effective phase, deadline): the first step window runs
        under the compile budget when one is set."""
        if phase == "step" and not self._warmed \
                and self.deadlines["compile"] > 0.0:
            return "compile", self.deadlines["compile"]
        return phase, self.deadlines.get(phase, 0.0)

    @contextmanager
    def guard(self, phase: str = "step"):
        """Deadline-guard the enclosed blocking section."""
        eff, deadline = self._effective(phase)
        if deadline <= 0.0:
            try:
                yield
            finally:
                if phase == "step":
                    self._warmed = True
            return
        done = threading.Event()
        th = threading.Thread(target=self._monitor,
                              args=(eff, deadline, done),
                              name=f"watchdog-{eff}", daemon=True)
        th.start()
        try:
            yield
        finally:
            done.set()
            if phase == "step":
                self._warmed = True

    def _monitor(self, phase: str, deadline: float,
                 done: threading.Event):
        if done.wait(deadline):
            return                      # guarded section finished
        self.hangs += 1
        info = {"phase": phase, "deadline_s": deadline,
                "nstep": self._host.get("nstep"),
                "t": self._host.get("t")}
        dump = None
        try:
            dump = self._emergency_dump(phase, deadline)
        except Exception:
            pass
        tel = self.telemetry
        if tel is not None:
            try:
                tel.record_event("hang", phase=phase,
                                 deadline_s=deadline, dump=dump,
                                 **dict(self._host))
            except Exception:
                pass
        print(f" watchdog: phase {phase!r} exceeded {deadline:g}s "
              f"deadline at nstep={info['nstep']}; classifying as "
              "hang", flush=True)
        with _lock:
            _pending["hang"] = info
        main = threading.main_thread()
        if self._installed and main.is_alive():
            try:
                signal.pthread_kill(main.ident, signal.SIGALRM)
            except (OSError, ValueError):
                pass
        if done.wait(self.grace_s):
            return                      # soft interrupt worked
        if self.hard_exit:
            print(f" watchdog: hang uninterruptible after "
                  f"{self.grace_s:g}s grace; exiting "
                  f"{HANG_EXIT_CODE}", flush=True)
            os._exit(HANG_EXIT_CODE)

    def _emergency_dump(self, phase: str, deadline: float
                        ) -> Optional[str]:
        """Manifest-valid ``hang_NNNNN/`` diagnostics dump from the
        last fetched host state.  The ``hang_`` prefix keeps it out of
        ``scan_checkpoints`` (prefix ``output_``) — it documents the
        hang, it is never resumed from."""
        from ramses_tpu.resilience.checkpoint import finalize_checkpoint
        self._ndump += 1
        final = os.path.join(self.base_dir, f"hang_{self._ndump:05d}")
        stage = final + ".tmp"
        os.makedirs(stage, exist_ok=True)
        payload = {"phase": phase, "deadline_s": deadline,
                   "time_unix": time.time()}
        payload.update(self._host)
        with open(os.path.join(stage, "hang.json"), "w") as f:
            json.dump(payload, f, indent=1, default=str)
        meta = {"kind": "hang", "phase": phase}
        for k in ("nstep", "t"):
            if self._host.get(k) is not None:
                meta[k] = self._host[k]
        return finalize_checkpoint(stage, final, meta=meta)
