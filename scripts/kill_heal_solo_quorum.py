#!/usr/bin/env python3
"""Shows the race in the kill cell's rehearsal (PERF.md section 7, PR 51).

Runs a checkout's ``benchmarks/run.py`` unchanged, with listeners that write
to stderr, one ``RACE`` line each: every compile-cache miss with its thread
and call site, every quorum round trip over 0.2 s (asked, answered), every
trainer's construction, and after each step what the trainer predicts for the
next (``predict_single``: the fused program is dispatched speculatively).
``--delay S`` holds the WINDOW's replacement (group 1's third trainer) back by
``S`` seconds: the lighthouse then cuts the survivor's quorum without it, the
survivor commits alone with a communicator of 1, predicts a single-group step
and compiles ``fused`` for the first time inside the window, which the
rehearsal (whose replacement joined in time) never ran: ``correct`` false by
``programs_compiled_in_window`` where the compile cache has no ``fused`` yet
(give it an empty ``JAX_COMPILATION_CACHE_DIR``; where an earlier run left the
program there it is one more read from the cache and ``correct`` stays true).
Without ``--delay`` the ``quorum_rpc`` lines give the margin: the survivor's
answer time less the replacement's ask time.

  JAX_COMPILATION_CACHE_DIR=$(mktemp -d) JAX_PLATFORMS=cpu python3 \\
      scripts/kill_heal_solo_quorum.py --delay 12 -- \\
      --workload internlm2-1.8b.kill-heal-2g --seed 7 --seconds 2 --rehearse
  chiprun --chips 1 -- python3 scripts/kill_heal_solo_quorum.py -- \\
      --workload internlm2-1.8b.kill-heal-2g --seed 7 --seconds 45 --trace 1
"""
import argparse
import os
import sys
import threading
import time
import traceback

HERE = os.path.abspath(__file__)
ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
ap.add_argument("--root", default=os.path.dirname(os.path.dirname(HERE)),
                help="the checkout whose benchmarks/run.py runs")
ap.add_argument("--delay", type=float, default=0.0,
                help="seconds the window's replacement is held back")
ap.add_argument("rest", nargs=argparse.REMAINDER, help="-- run.py's options")
args = ap.parse_args()
root = os.path.abspath(args.root)
rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
# run.py starts itself anew for a mix's environment, by sys.orig_argv.
sys.orig_argv[:] = [sys.executable, HERE, "--root", root, "--delay",
                    str(args.delay), "--", *rest]
sys.path[:0] = [root, os.path.join(root, "benchmarks")]
os.chdir(root)

import jax.monitoring  # noqa: E402
from torchft_tpu import manager as _manager  # noqa: E402
from torchft_tpu.parallel import step as _step  # noqa: E402


def say(what: str) -> None:
    sys.stderr.write(f"RACE {time.monotonic():.3f} {what}\n")
    sys.stderr.flush()


def on_event(event: str, **_) -> None:
    kind = event.rsplit("/", 1)[-1]
    if kind not in ("cache_misses", "cache_hits"):
        return
    frames = [f for f in traceback.extract_stack()[:-1]
              if "/jax/" not in f.filename and "/flax/" not in f.filename]
    where = " <- ".join(f"{os.path.basename(f.filename)}:{f.lineno}:{f.name}"
                        for f in reversed(frames[-4:]))
    if kind == "cache_misses" or "step.py" in where:    # a step program
        say(f"{kind} thread={threading.current_thread().name} {where}")


jax.monitoring.register_event_listener(on_event)
_train_step, _init = _step.FTTrainer.train_step, _step.FTTrainer.__init__
_quorum, _made = _manager.Manager._async_quorum_inner, {}


def train_step(self, batch):
    out = _train_step(self, batch)
    m = self.manager
    say(f"after_step thread={threading.current_thread().name} "
        f"step={m.current_step()} predict_single={self._predict_single} "
        f"comm={m._comm.size()} participants={m.num_participants()}")
    return out


def init(self, *a, **k):
    who = threading.current_thread().name
    _made[who] = _made.get(who, 0) + 1
    if args.delay and who == "group-1" and _made[who] == 3:
        say(f"holding the window's replacement back {args.delay} s")
        time.sleep(args.delay)
    say(f"trainer_init_begin thread={who} n={_made[who]}")
    _init(self, *a, **k)
    say(f"trainer_init_end thread={who} n={_made[who]}")


def quorum(self, *a):
    t0 = time.monotonic()
    try:
        return _quorum(self, *a)
    finally:
        if time.monotonic() - t0 > 0.2:
            say(f"quorum_rpc replica={self._replica_id.split(':')[0]} "
                f"asked={t0:.3f} step={self._step}")


_step.FTTrainer.train_step, _step.FTTrainer.__init__ = train_step, init
_manager.Manager._async_quorum_inner = quorum

import run  # noqa: E402

sys.exit(run.main(rest, may_restart=True))
