"""Driver ``train_groups``: builds a cell's training job from its
configuration and traffic mix and drives its replica groups through set-up,
the measured window and the checks. A traffic mix names this file by
``"driver"``; ``run.py`` asks a driver's file for ``Host`` and
``make_lighthouse``.

The model is the configuration's builder's (``models/<builder>.py``); what
happens to the job at a protocol point is an event's (``events/<kind>.py``).

The job is the program's own: ``Transformer`` with ``flash_attention``,
``FTTrainer``, ``Manager``, ``HostCommunicator`` and the native
``Lighthouse``, through the entry points a user calls. The construction was
copied from ``chip_smoke.py`` (``group_trainer_factory``, ``make_manager``,
``Sync``; PR 26), because a later PR may change the program and may not change
the yardstick.

A *host* is one process that holds some of the job's replica groups as
threads: all of them where groups share a chip, one where every group has a
process and a chip of its own. Group 0 leads: after every step that all
groups took together it says whether another follows, so that every group
stops at the same step.
"""

from __future__ import annotations

import functools
import gc
import os
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax

from harness import reference, spec

ORACLE_STEPS = 2        # the oracle follows the job's first two steps
PHASE_LIMIT_S = 300.0   # no phase of any cell may take longer


def log(msg: str) -> None:
    import sys

    with _LOG_LOCK:
        sys.stdout.write(msg + "\n")
        sys.stdout.flush()


_LOG_LOCK = threading.Lock()
T_PROCESS_NS = time.monotonic_ns()   # run.py sets it to the process's start


def mark(what: str) -> None:
    """Where set-up's seconds go: one line a phase, by the process's clock."""
    log(f"  setup: {(time.monotonic_ns() - T_PROCESS_NS) / 1e9:7.2f} s "
        f"{what}")


class Sync:
    """Named events as files in one directory, so that groups can be threads
    of one process or processes of their own."""

    def __init__(self, directory: str) -> None:
        self.dir = directory

    def _path(self, name: str) -> str:
        return os.path.join(self.dir, name)

    def set(self, name: str, value: str = "") -> None:
        tmp = self._path(f".{name}.{os.getpid()}.{threading.get_ident()}")
        with open(tmp, "w") as f:
            f.write(value)
        os.replace(tmp, self._path(name))

    def get(self, name: str) -> Optional[str]:
        try:
            with open(self._path(name)) as f:
                return f.read()
        except FileNotFoundError:
            return None

    def wait(self, name: str, timeout: float = PHASE_LIMIT_S) -> str:
        deadline = time.monotonic() + timeout
        while True:
            value = self.get(name)
            if value is not None:
                return value
            if self.get("failed") is not None:
                raise RuntimeError("another group failed")
            if time.monotonic() > deadline:
                raise TimeoutError(f"waited {timeout:.0f} s for {name!r}")
            time.sleep(0.001)

    def barrier(self, name: str, gi: int, groups: int) -> None:
        self.set(f"{name}.{gi}")
        for k in range(groups):
            self.wait(f"{name}.{k}")


# ------------------------------------------------------------ the model

def run_config(cell: Any, rehearse: bool) -> Tuple[Dict[str, Any], int]:
    """The configuration and sequence length as this run uses them."""
    cfg = dict(cell.config)
    seq = int(cell.mix["seq"])
    if rehearse:
        model = spec.model_of(cfg)
        cfg.update(model.REHEARSE)
        seq = min(seq, model.REHEARSE_SEQ)
    return cfg, seq


def make_tx(mix: Mapping[str, Any]) -> Any:
    import optax

    opt = mix["optimizer"]
    return getattr(optax, opt["name"])(float(opt["lr"]))


def make_lighthouse(mix: Mapping[str, Any]) -> Any:
    from torchft_tpu import Lighthouse

    return Lighthouse(bind="127.0.0.1:0", **mix["lighthouse"])


def manager_factory(mix: Mapping[str, Any], lighthouse_addr: str, name: str
                    ) -> Callable:
    from torchft_tpu import HostCommunicator, Manager

    # Timeouts sized for gigabytes crossing the host every step and for
    # peers that compile for a minute (as chip_smoke.make_manager).
    kwargs = dict(min_replica_size=1, timeout_ms=600_000,
                  quorum_timeout_ms=600_000)
    kwargs.update(mix.get("manager", {}))
    return lambda load, save: Manager(
        comm=HostCommunicator(timeout_sec=600), load_state_dict=load,
        state_dict=save, replica_id=name, lighthouse_addr=lighthouse_addr,
        rank=0, world_size=1, **kwargs)


# ------------------------------------------------------- compile counting

class CompileCounter:
    """Programs compiled, and compiled programs read from the persistent
    cache, in this process so far."""

    def __init__(self) -> None:
        import jax.monitoring

        self.compiled: Dict[str, int] = {}   # by the compiling thread's name
        self.read = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            who = threading.current_thread().name
            self.compiled[who] = self.compiled.get(who, 0) + 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.read += 1

    def snapshot(self) -> Tuple[Dict[str, int], int]:
        return dict(self.compiled), self.read


# --------------------------------------------------------------- a host

class Host:
    """One process's part of a run: the groups it holds, what they share and
    what they record."""

    def __init__(self, cell: Any, seed: int, seconds: float, trace: bool,
                 rehearse: bool, groups_here: List[int], sync: Sync,
                 lighthouse_addr: str) -> None:
        self.cell, self.mix = cell, cell.mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.rehearse = rehearse
        self.groups_here = groups_here
        self.n_groups = int(self.mix["groups"])
        self.sync, self.lighthouse_addr = sync, lighthouse_addr
        self.cfg, self.seq = run_config(cell, rehearse)
        self.batch = int(self.mix["batch_per_group"])
        self.device = jax.devices()[0]
        self.model = spec.model_of(self.cfg)
        self.loss_fn = self.model.make_loss_fn(self.cfg, self.seq,
                                               interpret=rehearse)
        self.tx = make_tx(self.mix)
        self.counter = CompileCounter()
        self.lock = threading.Lock()
        self.init_lock = threading.Lock()
        # Filled while running; read by the metrics.
        self.rec: Dict[str, Any] = {
            "steps": {}, "events": {}, "counters": {}, "spans": [],
            "checks": {}, "digests": {}, "errors": []}
        self.oracle: Optional[Dict[str, Any]] = None
        self.profile: Optional[Dict[str, Any]] = None
        self._trace_dir: Optional[str] = None

    # -- data and trainers

    def tokens(self, gi: int, index: int) -> Dict[str, Any]:
        return {"tokens": reference.make_tokens(
            self.cfg, self.seed, gi, index, self.batch, self.seq)}

    def make_trainer(self, gi: int, life: int) -> Any:
        from torchft_tpu.parallel import FTTrainer

        # Weights at init are made anew for every trainer and dropped once
        # it has its copy: two groups' state leaves no room to keep them.
        with self.init_lock, jax.default_device(self.device):
            params = reference.init_params(self.model, self.cfg, self.seed)
            return FTTrainer(
                loss_fn=self.loss_fn, tx=self.tx, params=params,
                manager_factory=manager_factory(
                    self.mix, self.lighthouse_addr, f"g{gi}_{life}"))

    # -- checks made before any trainer exists (the device is empty)

    def check_against_reference(self) -> None:
        """(d): the program's loss and gradients of one seeded sequence at
        the cell's widths and sequence length against the plain float32
        reference."""
        params = reference.init_params(self.model, self.cfg, self.seed)
        toks = reference.make_tokens(self.cfg, self.seed, 0, 0, 1, self.seq)
        want_loss, want = reference.loss_and_grads(self.model, self.cfg)(
            params, toks)
        got_loss, got = jax.jit(jax.value_and_grad(self.loss_fn))(
            params, {"tokens": toks})
        self.rec["checks"]["grad_vs_reference"] = reference.grad_distance(
            got, want)
        self.rec["checks"]["loss_vs_reference"] = abs(
            float(got_loss) - float(want_loss)) / abs(float(want_loss))

    def make_oracle(self) -> None:
        """(c): what the job's first two steps must give. The first is the
        protocol's init sync, in which only the primary's gradients count;
        in the second every group's do. The seeded tree gets no name here:
        it is the oracle's to let go (``reference.oracle_steps``)."""
        everyone = list(range(self.n_groups))
        batches = [[self.tokens(g, k)["tokens"] for g in everyone]
                   for k in range(ORACLE_STEPS)]
        self.oracle = reference.oracle_steps(
            self.loss_fn, self.tx,
            reference.init_params(self.model, self.cfg, self.seed),
            batches, [[0], everyone])
        log(f"  the oracle's sample: "
            f"{sum(x.nbytes for x in self.oracle['sample']) / 2**30:.3f} GiB")

    # -- one step

    def step(self, gi: int, trainer: Any, index: int, phase: str,
             life: int) -> Dict[str, Any]:
        batch = self.tokens(gi, index)
        t0 = time.monotonic_ns()
        loss, committed = trainer.train_step(batch)
        jax.block_until_ready((loss, trainer.params))
        t1 = time.monotonic_ns()
        m = trainer.manager
        rec = {"phase": phase, "life": life, "t0": t0, "t1": t1,
               "step": m.current_step(), "committed": bool(committed),
               "world": m.num_participants(),
               "timings": dict(trainer.last_step_timings)}
        if not committed:
            rec["error"] = repr(m.errored())
        with self.lock:
            self.rec["steps"].setdefault(gi, []).append(rec)
        if (t1 - t0) > 1e9 or not committed:   # the slow steps, one line each
            log(f"  group {gi}.{life} {phase}: step {rec['step']}"
                f"{'' if committed else ' ABORTED ' + rec['error']}, "
                f"{rec['world']} participating, {(t1 - t0) / 1e9:.2f} s")
        return rec

    # -- a group's life

    def run_group(self, gi: int) -> None:
        try:
            self._run_group(gi)
        except BaseException as e:  # noqa: BLE001 — reported by the host
            traceback.print_exc()
            with self.lock:
                self.rec["errors"].append(f"group {gi}: {e!r}")
            self.sync.set("failed", repr(e))

    def _run_group(self, gi: int) -> None:
        n, sync, mix = self.n_groups, self.sync, self.mix
        lead = gi == 0
        st = {"trainer": self.make_trainer(gi, 0), "life": 0, "index": 0}
        try:
            sync.barrier("ready", gi, n)
            if lead:
                mark("trainers built")
                self.memory("the trainers' making")
            warm = mix["warmup"]
            self._phase(gi, st, "warmup", events=[],
                        min_joint=int(warm["joint_steps"]), seconds=0.0,
                        first_step_check=lead)
            if warm.get("rehearse_events") and mix["events"]:
                self._phase(gi, st, "rehearsal", seconds=0.0,
                            events=[{**e, **warm.get("rehearse_with", {})}
                                    for e in mix["events"]],
                            min_joint_after=1)
            sync.barrier("warm", gi, n)
            if lead:
                mark("warm-up done")
                self.memory("warm-up")
            first_here = gi == min(self.groups_here)
            if first_here:
                self.rec["compiles_begin"] = self.counter.snapshot()
            if lead:
                self._window_begins(st["trainer"])
            sync.barrier("go", gi, n)
            self._phase(gi, st, "window", events=mix["events"],
                        min_joint=1, min_joint_after=2,
                        seconds=self.seconds)
            sync.barrier("done", gi, n)
            if first_here:
                self.rec["compiles_end"] = self.counter.snapshot()
            if lead:
                self._window_ends(st["trainer"])
            with self.lock:
                self.rec["digests"][gi] = reference.leaf_digests(
                    st["trainer"].params)
            self.keep_counters(f"end.{gi}.{st['life']}",
                               st["trainer"].manager.metrics())
        finally:
            if st["trainer"] is not None:
                st["trainer"].shutdown()
                st["trainer"] = None
            gc.collect()

    def _phase(self, gi: int, st: Dict[str, Any], phase: str,
               events: List[Dict[str, Any]], seconds: float,
               min_joint: int = 0, min_joint_after: int = 0,
               first_step_check: bool = False) -> None:
        """Step until the leader says stop. The leader stops once
        ``seconds`` have passed, ``min_joint`` steps were taken by all
        groups together and, where the phase has an event, ``min_joint_after``
        of them after the recovery."""
        n, sync = self.n_groups, self.sync
        lead = gi == 0
        happening = [spec.module("events", e["kind"]).Event(e, self, gi, phase)
                     for e in events]
        joint = joint_after = 0
        t_begin = time.monotonic()
        if lead:
            self.event(f"{phase}.begin", time.monotonic_ns())
        while True:
            if time.monotonic() - t_begin > PHASE_LIMIT_S:
                raise TimeoutError(f"group {gi}: phase {phase} did not end "
                                   f"in {PHASE_LIMIT_S:.0f} s")
            for ev in happening:
                ev.before_step(st)
            r = self.step(gi, st["trainer"], st["index"], phase, st["life"])
            st["index"] += 1
            if first_step_check and st["index"] <= ORACLE_STEPS:
                self._check_first_steps(st["trainer"], r, st["index"])
            if lead and self.profile is not None:
                self._maybe_stop_trace()
            is_joint = r["committed"] and r["world"] == n
            joint += is_joint
            set_off = [ev.after_step(st, r, is_joint, joint)
                       for ev in happening]
            if not is_joint:
                continue
            if any(set_off):
                # Nobody waits for a decision after this step.
                joint_after = 0
                continue
            settled = all(ev.settled for ev in happening)
            if happening and settled:
                joint_after += 1
            key = f"{phase}.decide.{r['step']}"
            if lead:
                stop = (time.monotonic() - t_begin >= seconds
                        and joint >= min_joint and settled
                        and joint_after >= (min_joint_after
                                            if happening else 0))
                if n > 1:
                    sync.set(key, "stop" if stop else "go")
            else:
                stop = sync.wait(key) == "stop"
            if stop:
                if lead:
                    self.event(f"{phase}.end", r["t1"])
                return

    def keep_counters(self, name: str, counters: Mapping[str, Any]) -> None:
        with self.lock:
            self.rec["counters"][name] = dict(counters)

    def memory(self, moment: str) -> int:
        """The device's peak so far, one line a ``moment``: the phase that
        ``peak_hbm_gib`` reads is the last one that raised it."""
        stats = self.device.memory_stats() or {}
        peak, now = (stats.get(k) or 0
                     for k in ("peak_bytes_in_use", "bytes_in_use"))
        log(f"  memory after {moment}: peak {peak / 2**30:.3f} GiB, "
            f"{now / 2**30:.3f} GiB in use")
        return peak

    def event(self, name: str, t_ns: int, first: bool = False) -> None:
        with self.lock:
            if first and name in self.rec["events"]:
                return
            self.rec["events"][name] = t_ns

    # -- the leader's extra duties

    def _check_first_steps(self, trainer: Any, r: Dict[str, Any],
                           taken: int) -> None:
        if self.oracle is None:
            return
        if not (r["committed"] and r["world"] == self.n_groups):
            self.rec["checks"]["state_vs_oracle"] = float("inf")
            self.oracle = None
            return
        if taken < ORACLE_STEPS:
            return
        got = reference.sample_state(trainer.state_dict())
        self.rec["checks"]["state_vs_oracle"] = reference.state_distance(
            got, self.oracle)
        self.oracle = None

    def _window_begins(self, trainer: Any) -> None:
        gc.collect()
        self.rec["counters"]["begin.0"] = trainer.manager.metrics()
        # From here to the window's end every compilation is named on
        # standard error: there should be none.
        jax.config.update("jax_log_compiles", True)
        if self.trace:
            self._start_trace()
        self.event("window.t0", time.monotonic_ns())

    def _window_ends(self, trainer: Any) -> None:
        self.event("window.t1", time.monotonic_ns())
        jax.config.update("jax_log_compiles", False)
        if self.profile is not None and self.profile.get("hi") is None:
            self._stop_trace()
        # Spans of every manager this host still holds are read by the
        # groups themselves; the leader's are the ones the metrics read.
        self.rec["spans"] = trainer.manager.tracer().spans()

    def _start_trace(self) -> None:
        import tempfile

        from harness.trace_reduce import MARK

        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(MARK):
            mark = time.monotonic_ns()
        self.profile = {"mark": mark, "lo": time.monotonic_ns(), "hi": None}

    def _maybe_stop_trace(self) -> None:
        limit = self.mix.get("trace_seconds")
        if self.profile["hi"] is None and limit is not None and \
                time.monotonic_ns() - self.profile["lo"] >= limit * 1e9:
            self._stop_trace()

    def _stop_trace(self) -> None:
        import glob

        self.profile["hi"] = time.monotonic_ns()
        jax.profiler.stop_trace()
        self.profile["stopped"] = time.monotonic_ns()
        found = glob.glob(os.path.join(
            self._trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        self.profile["xplane"] = found[0] if found else None

    # -- after the window

    def raw_loop(self, steps: int = 20) -> List[float]:
        """The same model, optimizer and batch in a plain jitted loop with
        no Manager: what a step costs with fault tolerance off. Seconds per
        step."""
        import optax

        params = reference.init_params(self.model, self.cfg, self.seed)
        opt = self.tx.init(params)
        batch = self.tokens(0, 0)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(p, o, b):
            loss, g = jax.value_and_grad(self.loss_fn)(p, b)
            updates, o = self.tx.update(g, o, p)
            return optax.apply_updates(p, updates), o, loss

        walls = []
        for i in range(steps + 2):
            t0 = time.perf_counter()
            params, opt, loss = step(params, opt, batch)
            jax.block_until_ready((params, loss))
            if i >= 2:
                walls.append(time.perf_counter() - t0)
        return walls

    def run(self) -> Dict[str, Any]:
        """Everything this host does, in order. Returns its record."""
        lead = 0 in self.groups_here
        mark("devices found, model built")
        if lead:
            self.check_against_reference()
            mark("gradients compared with the reference")
            self.memory("the reference check")
            self.make_oracle()
            mark("oracle made")
            self.memory("the oracle")
            gc.collect()
        threads = [threading.Thread(target=self.run_group, args=(gi,),
                                    name=f"group-{gi}")
                   for gi in self.groups_here]
        for t in threads:
            t.start()
        for t in threads:
            t.join(4 * PHASE_LIMIT_S)
        if any(t.is_alive() for t in threads):
            self.rec["errors"].append("a replica group hung")
            self.sync.set("failed", "hung")
        self.rec["memory_peak_bytes"] = self.memory("the window")
        if lead and self.trace and not self.rec["errors"]:
            self.rec["raw_walls"] = self.raw_loop()
        return self.rec
