"""Event ``kill``: a replica group stops the way a reclaimed machine stops
(no farewell reaches the lighthouse, heartbeats stop, sockets close), and a
replacement with weights at init is started at once, heals from a survivor
and rejoins. A traffic mix names this file by an event's ``"kind"``:

    {"kind": "kill", "victim": 1, "after_joint_step": 2}

The hard stop was copied from ``bench.py:_hard_kill_manager`` (PR 26): the
program has no public call for dying badly, so it reaches into ``Manager``'s
private attributes and fails loudly where one of them is gone, instead of
falling back to a clean shutdown that would measure something else.

What an event's file gives a driver: a class ``Event`` (one object per
replica group and phase; ``before_step``, ``after_step``, ``settled``),
``own_threads`` (threads whose compilations inside the window belong to the
event) and ``designed_abort_window`` (between which moments an aborted step
is the event's doing). It records its moments as ``<phase>.kill``,
``<phase>.replacement_built``, ``<phase>.survivor_commit`` and
``<phase>.recovered``.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, Mapping, Optional, Set, Tuple

_NEEDS = ("_manager_server", "_ckpt_server", "_comm", "_executor",
          "_put_executor")


def hard_stop(manager: Any) -> None:
    missing = [a for a in _NEEDS if not hasattr(manager, a)]
    server = getattr(manager, "_manager_server", None)
    if server is None or not hasattr(server, "hard_stop"):
        missing.append("_manager_server.hard_stop")
    if missing:
        raise AttributeError(
            f"benchmarks/events/kill.py: Manager no longer has {missing}; "
            f"the hard stop must be rewritten against the new internals "
            f"(or a public one, PERF.md Open questions)")
    server.hard_stop()                  # stops serving and beating, no farewell
    manager._ckpt_server.shutdown()     # heal streams close
    manager._comm.shutdown()            # ring sockets close
    manager._executor.shutdown(wait=False, cancel_futures=True)
    manager._put_executor.shutdown(wait=False)


def own_threads(spec: Mapping[str, Any]) -> Set[str]:
    """A replacement is a new trainer: what it compiles inside the window is
    part of what the recovery costs, and is counted apart."""
    return {f"group-{int(spec['victim'])}"}


def designed_abort_window(spec: Mapping[str, Any], events: Mapping[str, int],
                          phase: str = "window"
                          ) -> Optional[Tuple[int, int]]:
    """From the kill to the recovery; ``None`` while the job has not
    recovered."""
    a, b = events.get(f"{phase}.kill"), events.get(f"{phase}.recovered")
    return (a, b) if a is not None and b is not None else None


class Event:
    def __init__(self, spec: Mapping[str, Any], host: Any, gi: int,
                 phase: str) -> None:
        self.host, self.gi, self.phase = host, gi, phase
        self.after = int(spec["after_joint_step"])
        self.is_victim = int(spec["victim"]) == gi
        self.fired = False
        self.settled = False    # the job is back at full membership

    def before_step(self, st: Dict[str, Any]) -> None:
        pass

    def after_step(self, st: Dict[str, Any], r: Mapping[str, Any],
                   is_joint: bool, joint: int) -> bool:
        """``True`` where this step set the event off: nobody waits for a
        decision after it."""
        host, phase = self.host, self.phase
        if not self.fired:
            if not (is_joint and joint == self.after):
                return False
            self.fired = True
            if self.is_victim:
                self._kill_and_replace(st)
            else:
                host.sync.wait(f"{phase}.killed")
            return True
        if not self.settled:
            if is_joint:
                self.settled = True
                if self.is_victim:
                    host.event(f"{phase}.recovered", r["t1"])
            elif self.gi == 0 and r["committed"]:
                host.event(f"{phase}.survivor_commit", r["t1"], first=True)
        return False

    def _kill_and_replace(self, st: Dict[str, Any]) -> None:
        host, gi, phase = self.host, self.gi, self.phase
        victim = st["trainer"]
        host.keep_counters(f"killed.{phase}.{gi}", victim.manager.metrics())
        hard_stop(victim.manager)
        host.event(f"{phase}.kill", time.monotonic_ns())
        host.sync.set(f"{phase}.killed")
        st["trainer"] = None
        # Nothing may say goodbye for the dead (Manager.shutdown would), so
        # its threads stay as they are; its weights are let go by hand.
        victim.params = victim.opt_state = None
        del victim
        gc.collect()
        st["life"] += 1
        st["trainer"] = host.make_trainer(gi, st["life"])
        host.event(f"{phase}.replacement_built", time.monotonic_ns())
