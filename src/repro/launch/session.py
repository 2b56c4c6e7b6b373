"""FleetSession — the long-running fleet serving loop (ROADMAP item 4).

The batch drivers run K rounds and exit; the paper's setting is the
opposite — a fleet of agents at physical locations streaming
observations into a learner indefinitely, with budgets *monitored over
time* (adaptive scheduling only pays off in that regime).  A
``FleetSession`` is that loop: continuous per-round observation batches
fed into the single-compile triggered train step, with every round's
CommStats folded into a live :class:`repro.comm.rollup.CommRollup`
that HTTP scrapes and file sinks read while training runs.

Overlap discipline (the double buffer): the jitted step is dispatched
asynchronously (JAX returns futures), the NEXT round's observation
batch is drawn while the device works, and only then are the finished
round's metrics pulled — sampling and telemetry ride inside the device
step's shadow instead of serializing after it.  The session jits the
step so that a round's metrics come home as one flat buffer per dtype
(:func:`pack_metrics`), whose copy to the host is started as the step
is dispatched: the pull is one host transfer per dtype, not one per
metric, and the dict handed to the rollup and ``on_round`` is the same
tree of host numpy arrays ``jax.device_get`` would give.
The round sampler is compiled once per session (``jit(sample_round)``
over the base key and the traced round index), so drawing a round is
one call to a cached program that queues behind the step, not an eager
trace of the sampler's ops; ``batch_fn`` must therefore be a pure JAX
function of its arguments.
Each stage of a round (sample, dispatch, wait, pull, rollup,
checkpoint) runs inside a profiler span ``fleet.<stage>`` carrying the
round index (the pull's also the count of buffers, ``buffers=<n>``),
and its seconds accumulate in the rollup's ``stage_seconds``.  The
step donates its TrainState argument (``donate_argnums=(0,)``), so
steady-state serving allocates no new state buffers on backends that
support donation.

Run modes:

* ``run(rounds)`` — blocking loop, ``rounds=0`` means until ``stop()``.
* ``start()`` / ``stop()`` — the same loop on a daemon thread, for
  embedding under a CLI that also serves HTTP.

``serve_telemetry()`` attaches a :class:`TelemetryServer` exposing
``/stats.json`` (rollup snapshot) and ``/metrics`` (Prometheus text);
``python -m repro.launch.serve --fleet`` is the CLI around all of this.

Durability (DESIGN.md §10): a :class:`SessionOptions` with ``ckpt_dir``
set arms crash-safe checkpointing through ``repro.checkpoint`` — every
``ckpt_every`` rounds the TrainState, PRNG stream, round index and a
rollup snapshot are written atomically, and a relaunched session
auto-resumes from the latest complete checkpoint with a bit-equal
observation stream (the batch key fold continues at the restored round
index) and strictly monotone rollup counters.  ``watchdog_timeout``
arms a :class:`Watchdog` that flags stalled device dispatch as a
``"stall"`` degradation event without killing the loop.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import math
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.rollup import CommRollup
from repro.core.frontier import batch_fn_arity


@dataclasses.dataclass(frozen=True)
class SessionOptions:
    """Durability knobs for a :class:`FleetSession`.

    ckpt_dir:
        Checkpoint directory; ``None`` (default) disables checkpointing
        and resume entirely — the session is byte-for-byte the
        pre-durability loop.
    ckpt_every:
        Write a checkpoint every N completed rounds (0 = only explicit
        :meth:`FleetSession.checkpoint` calls).
    resume:
        Auto-restore from the latest complete checkpoint under
        ``ckpt_dir`` at construction time (no-op when none exists).
    watchdog_timeout:
        Seconds without a completed round before the watchdog records a
        ``"stall"`` degradation event (0 disables the watchdog).
    """

    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0
    resume: bool = True
    watchdog_timeout: float = 0.0


class Watchdog:
    """Flags stalled round dispatch as rollup degradation events.

    The serving loop calls :meth:`beat` after every completed round;
    :meth:`check` compares the time since the last beat against
    ``timeout`` and records one ``"stall"`` event per stall episode
    (re-armed by the next beat) — the session keeps running, the event
    stream is the signal.  ``check`` takes an explicit ``now`` so tests
    drive it synchronously; :meth:`start` runs it on a daemon thread.
    """

    def __init__(self, rollup: CommRollup, timeout: float, *,
                 clock=time.monotonic):
        self.rollup = rollup
        self.timeout = float(timeout)
        self._clock = clock
        self._last = clock()
        self._flagged = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat(self) -> None:
        self._last = self._clock()
        self._flagged = False

    def check(self, now: Optional[float] = None) -> bool:
        """Returns True iff this call newly flagged a stall."""
        now = self._clock() if now is None else now
        if not self._flagged and now - self._last > self.timeout:
            self._flagged = True
            self.rollup.record_degradation("stall")
            return True
        return False

    def start(self) -> None:
        def _loop():
            while not self._stop.wait(max(self.timeout / 4.0, 0.01)):
                self.check()

        self._thread = threading.Thread(
            target=_loop, name="fleet-watchdog", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(5.0)
            self._thread = None


def pack_metrics(step_fn: Callable):
    """Wrap ``step_fn`` so that its metrics leave the device as one flat
    buffer per dtype; returns ``(packed_step, unpack)``.

    ``packed_step(*args)`` returns ``(state, buffers)``: the raveled
    leaves of each dtype of ``step_fn``'s metric tree, concatenated in
    leaf order, one buffer per dtype in order of first appearance.  It
    keeps ``step_fn``'s name (the jitted module stays
    ``jit_<name>``).  The layout (treedef, each leaf's buffer, offset and
    shape) is recorded while it traces, so packing adds no trace or
    compile; the step's metric tree must keep one structure across
    traces, as a session's does.  ``unpack(buffers)`` reads each buffer
    to the host once and returns the metric tree of host numpy arrays
    with the keys, shapes, dtypes and bits ``jax.device_get`` gives.
    """
    layout = []

    @functools.wraps(step_fn)
    def packed_step(*args, **kwargs):
        state, metrics = step_fn(*args, **kwargs)
        leaves, treedef = jax.tree_util.tree_flatten(metrics)
        groups: dict = {}
        slots = []
        for leaf in map(jnp.asarray, leaves):
            parts = groups.setdefault(leaf.dtype, [])
            slots.append((list(groups).index(leaf.dtype),
                          sum(p.size for p in parts), leaf.shape))
            parts.append(leaf.ravel())
        layout[:] = [treedef, slots]
        return state, tuple(jnp.concatenate(p) for p in groups.values())

    def unpack(buffers):
        treedef, slots = layout
        host = [np.asarray(b) for b in buffers]
        return treedef.unflatten(
            [host[i][off:off + math.prod(shape)].reshape(shape)
             for i, off, shape in slots])

    return packed_step, unpack


class _StageClock:
    """Times the stages of served rounds.

    ``with clock(stage, k, **meta):`` opens a profiler span
    ``fleet.<stage>`` carrying the round index (``round=k``) and any
    further ``meta``, and adds the block's ``perf_counter`` time to the
    stage's running total; :meth:`take` hands the totals over and starts
    new ones.  The spans land in the same trace as the device's ops (an
    inactive profiler makes them near-free); the totals feed the
    rollup's ``stage_seconds``.
    """

    def __init__(self):
        self._seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, stage: str, k: int, **meta):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"fleet.{stage}", round=k, **meta):
            yield
        self._seconds[stage] = (self._seconds.get(stage, 0.0)
                                + time.perf_counter() - t)

    def take(self) -> dict:
        out, self._seconds = self._seconds, {}
        return out


class FleetSession:
    """Continuous train-on-arrival loop over a triggered train step.

    Parameters
    ----------
    step_fn:
        The UNjitted ``(state, batch) -> (state, metrics)`` train step
        (``make_triggered_train_step`` output); the session jits it
        with a donated state argument, its metrics packed into one
        device buffer per dtype (:func:`pack_metrics`) so that a round's
        pull is one transfer per dtype, started at dispatch.
    state:
        Initial TrainState (``init_train_state``).
    batch_fn:
        ``batch_fn(round_key) -> batch`` — one round's per-agent
        observation batch from ``fold_in(key, k)``; a two-argument
        ``batch_fn(round_key, k)`` also receives the absolute round
        index ``k`` (fault schedules, drifting targets).  It must be a
        pure JAX function of its arguments: the session compiles it
        once, with ``k`` traced, so Python side effects run only while
        it traces and the same ``(key, k)`` always gives the same batch
        (which resume bit-equality relies on).
    rollup:
        The :class:`CommRollup` every round's metrics stream into.
    key:
        Base PRNG key for the observation stream.
    on_round:
        Optional ``on_round(round_index, metrics_dict)`` host callback
        (logging, file sinks); runs outside the rollup lock.  The dict
        holds host numpy arrays, as ``jax.device_get`` of the step's
        metrics would.
    options:
        :class:`SessionOptions` durability knobs.  When ``ckpt_dir`` is
        set and ``resume`` is on, construction restores the latest
        complete checkpoint (state, PRNG stream, round index, rollup)
        before the first round runs.
    """

    def __init__(self, step_fn: Callable, state, batch_fn: Callable,
                 rollup: CommRollup, *, key=None,
                 on_round: Optional[Callable] = None,
                 options: Optional[SessionOptions] = None):
        packed_step, self._unpack = pack_metrics(step_fn)
        self._step = jax.jit(packed_step, donate_argnums=(0,))
        self._state = state
        self._batch_fn = batch_fn
        with_round = batch_fn_arity(batch_fn) == 2

        # the key is an argument, not a closure: a resume replaces it
        def sample_round(key, k):
            round_key = jax.random.fold_in(key, k)
            if with_round:
                return batch_fn(round_key, k)
            return batch_fn(round_key)

        self._sample = jax.jit(sample_round)
        self.rollup = rollup
        self._key = key if key is not None else jax.random.key(0)
        self._on_round = on_round
        self.options = options or SessionOptions()
        self._round = 0
        self._watchdog: Optional[Watchdog] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        if self.options.ckpt_dir and self.options.resume:
            self._try_resume()

    @property
    def state(self):
        """The latest TrainState (safe to read between rounds; racy but
        harmless mid-round — JAX arrays are immutable snapshots)."""
        return self._state

    @property
    def round_index(self) -> int:
        """The next round to run (== rounds completed this lineage,
        across restarts)."""
        return self._round

    # -- durability ----------------------------------------------------

    def _ckpt_tree(self):
        """The pytree a session checkpoint round-trips: the full
        TrainState (params/opt/EF/ctrl/net_state — tuple-shaped
        net_state included) plus the raw PRNG key data."""
        return {"state": self._state,
                "key": jax.random.key_data(self._key)}

    def checkpoint(self) -> Optional[int]:
        """Atomically persist the session at its current round; returns
        the checkpoint step (the round index) or None when disabled."""
        if not self.options.ckpt_dir:
            return None
        from repro import checkpoint as ckpt

        tree = jax.device_get(self._ckpt_tree())
        extra = {"round": self._round, "rollup": self.rollup.state_dict()}
        ckpt.save(self.options.ckpt_dir, self._round, tree, extra=extra)
        return self._round

    def _try_resume(self) -> None:
        from repro import checkpoint as ckpt

        step = ckpt.latest_step(self.options.ckpt_dir)
        if step is None:
            return
        tree = ckpt.restore(self.options.ckpt_dir, self._ckpt_tree(),
                            step=step)
        extra = ckpt.read_manifest(
            self.options.ckpt_dir, step=step).get("extra") or {}
        self._state = tree["state"]
        self._key = jax.random.wrap_key_data(tree["key"])
        self._round = int(extra.get("round", step))
        if extra.get("rollup"):
            self.rollup.load_state(extra["rollup"])
        self.rollup.record_restart()

    def run(self, rounds: int = 0) -> int:
        """Blocking serve loop; returns the number of rounds executed.

        ``rounds=N`` runs N MORE rounds from the current (possibly
        resumed) position; ``rounds=0`` runs until :meth:`stop` is
        called (or KeyboardInterrupt).  The observation stream is keyed
        by absolute round index, so a resumed session consumes exactly
        the batches the killed one would have.
        """
        opts = self.options
        start = self._round
        target = 0 if rounds == 0 else start + rounds
        k = start
        if opts.watchdog_timeout > 0:
            self._watchdog = Watchdog(self.rollup, opts.watchdog_timeout)
            self._watchdog.start()
        stage = _StageClock()
        try:
            with stage("sample", k):
                batch = self._sample(self._key, k)
            while not self._stop.is_set() and (target == 0 or k < target):
                # 1. dispatch round k (async — returns device futures);
                # its metric buffers start home as soon as the step ends
                with stage("dispatch", k):
                    self._state, buffers = self._step(self._state, batch)
                    for b in buffers:
                        b.copy_to_host_async()
                # 2. sample round k+1's observations in the device's shadow
                # (one dispatch, queued behind step k)
                if target == 0 or k + 1 < target:
                    with stage("sample", k + 1):
                        batch = self._sample(self._key, k + 1)
                # 3. wait for round k on the device, pull its metrics
                # (the transfers alone), roll up
                with stage("wait", k):
                    jax.block_until_ready(buffers)
                with stage("pull", k, buffers=len(buffers)):
                    metrics = self._unpack(buffers)
                with stage("rollup", k):
                    self.rollup.update(metrics)
                if self._watchdog is not None:
                    self._watchdog.beat()
                if self._on_round is not None:
                    self._on_round(k, metrics)
                k += 1
                self._round = k
                if (opts.ckpt_dir and opts.ckpt_every > 0
                        and (k - start) % opts.ckpt_every == 0):
                    with stage("checkpoint", k - 1):
                        self.checkpoint()
                self.rollup.record_stage_seconds(stage.take())
        finally:
            if self._watchdog is not None:
                self._watchdog.stop()
                self._watchdog = None
        return k - start

    # -- thread mode ---------------------------------------------------

    def start(self, rounds: int = 0) -> None:
        """Run the serve loop on a daemon thread."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("session already running")
        self._stop.clear()

        def _target():
            try:
                self.run(rounds)
            except BaseException as e:  # surfaced by stop()/join()
                self._error = e

        self._thread = threading.Thread(
            target=_target, name="fleet-session", daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 30.0) -> None:
        """Signal the loop to finish its round and join the thread."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def serve_telemetry(self, port: int = 0) -> "TelemetryServer":
        """Start an HTTP telemetry endpoint over this session's rollup."""
        server = TelemetryServer(self.rollup, port=port)
        server.start()
        return server


# ----------------------------------------------------------------------
# telemetry sinks
# ----------------------------------------------------------------------


class TelemetryServer:
    """Threaded HTTP exporter: ``/stats.json`` + Prometheus ``/metrics``.

    ``port=0`` binds an ephemeral port (read it back from ``.port``) —
    the mode tests and parallel CI lanes use.
    """

    def __init__(self, rollup: CommRollup, *, port: int = 0,
                 host: str = "127.0.0.1"):
        self.rollup = rollup

        roll = rollup

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (stdlib casing)
                if self.path in ("/", "/stats.json", "/stats"):
                    body = roll.to_json().encode()
                    ctype = "application/json"
                elif self.path == "/metrics":
                    body = roll.to_prometheus().encode()
                    ctype = "text/plain; version=0.0.4"
                else:
                    self.send_error(404)
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # quiet scrape spam
                pass

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._thread: Optional[threading.Thread] = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="fleet-telemetry",
            daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(5.0)
            self._thread = None


def file_sink(path: str, rollup: CommRollup, every: int = 50):
    """An ``on_round`` callback writing rollup snapshots to ``path``.

    Atomic-enough for CI consumption: a whole snapshot is written each
    ``every`` rounds via replace, so a concurrent reader never sees a
    torn file.
    """
    import os

    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)

    def _write():
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            f.write(rollup.to_json())
        os.replace(tmp, path)

    def _cb(k, metrics):
        if (k + 1) % every == 0:
            _write()

    _cb.flush = _write
    return _cb


# ----------------------------------------------------------------------
# scenario builder: the m=64 tiered linreg fleet
# ----------------------------------------------------------------------


def build_linreg_fleet_session(
    net=None, cfg_lr=None, *, lam_base: float = 1.0, seed: int = 0,
    mesh=None, window: int = 64, clock=time.monotonic,
    on_round: Optional[Callable] = None,
    options: Optional[SessionOptions] = None,
) -> FleetSession:
    """A :class:`FleetSession` serving the paper's linreg fleet.

    Defaults to the budget-adaptive m=64 smart-city scenario
    (``TIERED_M64_ADAPTIVE`` over ``TIERED_M64_CFG``): closed-loop
    controllers give the rollup live λ trajectories, and per-tier
    budgets arm the violation counters.  ``mesh`` routes through
    ``StepOptions.mesh`` to the fleet-sharded step.
    """
    from repro.configs.base import TrainConfig
    from repro.configs.paper_linreg import TIERED_M64_ADAPTIVE, TIERED_M64_CFG
    from repro.core import regression as R
    from repro.core.api import (
        StepOptions,
        init_train_state,
        make_triggered_train_step,
    )
    from repro.optim import optimizers as opt_lib

    net = net or TIERED_M64_ADAPTIVE
    cfg_lr = cfg_lr or TIERED_M64_CFG
    if net.num_agents != cfg_lr.num_agents:
        raise ValueError(
            f"network {net.name} has {net.num_agents} agents but problem "
            f"{cfg_lr.name} expects {cfg_lr.num_agents}")
    problem = R.make_problem(cfg_lr, jax.random.key(seed))

    def loss_fn(params, batch):
        xs, ys = batch
        r = xs @ params["w"] - ys
        return 0.5 * jnp.mean(r * r)

    cfg = TrainConfig(lr=cfg_lr.stepsize, optimizer="sgd",
                      num_agents=cfg_lr.num_agents,
                      comm=net.policies(lam_base=lam_base))
    opt = opt_lib.from_config(cfg)
    step_fn = make_triggered_train_step(
        loss_fn, opt, cfg,
        options=StepOptions(agent_metrics=True, mesh=mesh))
    state = init_train_state({"w": jnp.zeros(cfg_lr.n)}, opt, cfg)
    rollup = CommRollup(
        tier_names=tuple(t.name for t in net.tiers),
        tier_index=net.tier_index(),
        budgets=net.budgets(),
        window=window, clock=clock)
    return FleetSession(
        step_fn, state, lambda key: R.agent_batches(problem, key),
        rollup, key=jax.random.key(seed + 1), on_round=on_round,
        options=options)
