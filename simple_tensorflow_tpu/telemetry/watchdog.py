"""Wedge watchdog: snapshot every thread's stack when an armed
operation blows through its deadline.

A fused ``run_steps`` window or a serving batch that normally takes
milliseconds and suddenly takes minutes is WEDGED (device hang,
deadlock) — and by the time a human looks, the evidence is gone.
Callers arm the watchdog around such operations with a deadline derived
from their own trailing average:

    token = watchdog.arm("fused_window", deadline_s=..., n_steps=64)
    try:    ... run the window ...
    finally: watchdog.disarm(token)

A single monitor thread (``stf_telemetry_watchdog``, started lazily on
first arm) polls armed entries; the first poll past an entry's deadline
records a ``wedge`` flight event carrying EVERY live thread's stack
(stf threads flagged) and dumps the flight recorder to JSONL — the
``faulthandler``-style forensics the postmortem needs. Each armed entry
fires at most once.

Knobs (docs/OBSERVABILITY.md): ``STF_WATCHDOG_MULTIPLE`` (default 10
— deadline = multiple x the op's trailing average), ``STF_WATCHDOG_MIN_S``
(default 5 — floor, so jitter on fast ops never fires), ``STF_WATCHDOG=0``
disables arming entirely.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..platform import monitoring
from ..platform import sync as _sync
from . import recorder as _recorder_mod

_metric_wedges = monitoring.Counter(
    "/stf/telemetry/watchdog_wedges",
    "Armed operations that exceeded their wedge deadline (stacks "
    "snapshotted into the flight recorder)", "what")


def multiple() -> float:
    return float(os.environ.get("STF_WATCHDOG_MULTIPLE", "10"))


def min_deadline_s() -> float:
    return float(os.environ.get("STF_WATCHDOG_MIN_S", "5"))


def enabled() -> bool:
    return os.environ.get("STF_WATCHDOG", "1") != "0"


def deadline_for(trailing_avg_s: Optional[float]) -> Optional[float]:
    """The wedge deadline for an op whose trailing average duration is
    known: ``max(min_s, multiple * avg)``; None (don't arm) when there
    is no history yet — first calls legitimately include compiles."""
    if trailing_avg_s is None or trailing_avg_s <= 0:
        return None
    return max(min_deadline_s(), multiple() * trailing_avg_s)


class Watchdog:
    """See the module docstring. ``on_wedge`` callbacks (tests, custom
    pagers) run after the built-in record+dump."""

    POLL_S = 0.1

    def __init__(self):
        self._lock = _sync.Lock("telemetry/watchdog",
                                rank=_sync.RANK_STATE)
        self._armed: Dict[int, Dict[str, Any]] = {}
        self._next_token = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.on_wedge: List[Callable[[Dict[str, Any]], None]] = []
        self.wedges_detected = 0

    # -- arming ---------------------------------------------------------------
    def arm(self, what: str, deadline_s: float, **meta) -> Optional[int]:
        """Watch one operation: fire if it is still armed ``deadline_s``
        seconds from now. Returns a token for ``disarm`` (None when the
        watchdog is disabled or the deadline is absent)."""
        if deadline_s is None or deadline_s <= 0 or not enabled():
            return None
        with self._lock:
            self._next_token += 1
            token = self._next_token
            self._armed[token] = {
                "what": what, "armed_at": time.perf_counter(),
                "deadline": time.perf_counter() + float(deadline_s),
                "deadline_s": float(deadline_s),
                "thread": threading.current_thread().name,
                "fired": False, "meta": meta}
            self._ensure_thread()
        return token

    def disarm(self, token: Optional[int]) -> None:
        if token is None:
            return
        with self._lock:
            self._armed.pop(token, None)

    def armed_count(self) -> int:
        with self._lock:
            return len(self._armed)

    # -- monitor thread -------------------------------------------------------
    def _ensure_thread(self):
        # caller holds the lock. Each monitor thread gets its OWN stop
        # event, captured in its args: a stop() racing a concurrent
        # arm() then stops the OLD thread's event while the new thread
        # keeps its fresh one — an armed entry is never left silently
        # unmonitored
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, args=(self._stop,),
            name="stf_telemetry_watchdog", daemon=True)
        self._thread.start()

    def _loop(self, stop_event):
        while not stop_event.wait(self.POLL_S):
            now = time.perf_counter()
            due = []
            with self._lock:
                for token, e in self._armed.items():
                    if not e["fired"] and now > e["deadline"]:
                        e["fired"] = True
                        due.append((token, dict(e)))
            for token, e in due:
                self._fire(e)

    def _fire(self, entry: Dict[str, Any]):
        self.wedges_detected += 1
        _metric_wedges.get_cell(entry["what"]).increase_by(1)
        rec = _recorder_mod.get_recorder()
        overdue = time.perf_counter() - entry["armed_at"]
        # stacks carry per-thread held locks and the wait-for graph
        # names live lock cycles (stf.analysis.concurrency): a REAL
        # deadlock's wedge dump says WHO waits on WHAT held by WHOM
        rec.record("wedge", what=entry["what"],
                   armed_thread=entry["thread"],
                   deadline_s=entry["deadline_s"],
                   running_for_s=round(overdue, 3),
                   stacks=_recorder_mod.thread_stacks(),
                   wait_graph=_recorder_mod.wait_graph_record(),
                   **(entry["meta"] or {}))
        try:
            rec.dump(reason=f"wedge:{entry['what']}")
        except Exception:  # noqa: BLE001 — forensics must not raise
            pass
        for cb in list(self.on_wedge):
            try:
                cb(entry)
            except Exception:  # noqa: BLE001
                pass

    def stop(self, timeout: float = 5.0) -> None:
        """Stop the monitor thread and clear armed state (conftest leak
        hygiene; safe when never started). Arming again restarts it.
        The stop event is set UNDER the lock so an arm() racing this
        call either sees the cleared thread and spawns a fresh monitor
        (with its own event) or is serialized behind the teardown."""
        with self._lock:
            th = self._thread
            self._thread = None
            self._armed.clear()
            self._stop.set()
        if th is not None and th.is_alive() and \
                th is not threading.current_thread():
            th.join(timeout)


_WATCHDOG = Watchdog()


def get_watchdog() -> Watchdog:
    return _WATCHDOG
