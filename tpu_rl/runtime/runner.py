"""Process orchestration: spawn, supervise, and tear down the role processes.

Capability parity with the reference ``Runner`` (``/root/reference/main.py:62-524``):
role dispatch, spawn-start-method child processes, stop-event + signal/atexit
cleanup, per-child heartbeats — plus the part the reference ships commented
out (``main.py:417-473``, "probably shouldn't use, has issues"): a working
supervisor that terminates and respawns any child whose heartbeat goes silent,
with a restart budget. Learner children resume from their newest checkpoint on
respawn (``checkpoint.py``), so supervision composes with resume.

Roles (reference CLI ``main.py:475-508``):
- ``learner``  : LearnerStorage + LearnerService sharing a shm store + stat
  mailbox (reference ``learner_sub_process``, ``main.py:301-414``)
- ``manager``  : one relay (reference ``manager_sub_process``)
- ``worker``   : ``num_p`` actor processes (reference ``worker_sub_process``)
- ``local``    : everything on one host — the smallest real cluster

One process owns a chip. Workers/managers/storage/fleet replicas are CPU
processes: the supervisor starts them with ``JAX_PLATFORMS=cpu`` in their
environment, so only the learner (or the colocated loop) opens the
accelerator. The supervising parent itself never initialises a jax backend.
"""

from __future__ import annotations

import contextlib
import functools
import multiprocessing as mp
import os
import signal
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from tpu_rl.config import Config, MachinesConfig
from tpu_rl.data.layout import BatchLayout
from tpu_rl.data.shm_ring import alloc_handles
from tpu_rl.runtime.mailbox import STAT_SLOTS

# Supervision defaults. Deployments override these via Config
# (heartbeat_timeout_s / startup_grace_s / supervise_poll_s / max_restarts /
# restart_*) — see Supervisor.from_config; the constants remain the
# dataclass defaults so direct Supervisor() construction keeps working.
HEARTBEAT_TIMEOUT = 60.0  # seconds of silence before a child is declared dead
STARTUP_GRACE = 180.0  # extra silence allowed after (re)start: jax import +
# XLA compile legitimately take minutes before the first loop heartbeat
SUPERVISE_POLL = 2.0
RESTART_WINDOW = 300.0  # sliding window for the restart budget
RESTART_BACKOFF = 1.0  # base respawn delay within a crash streak
RESTART_BACKOFF_MAX = 30.0


def owner_on_cpu(cfg: Config) -> bool:
    """Will this config's accelerator-owning child (learner, colocated loop,
    PBT member) run on the CPU on purpose? ``learner_device="cpu"``, or a
    ``JAX_PLATFORMS=cpu`` environment the child inherits."""
    from tpu_rl.utils.platform import cpu_requested

    return cfg.learner_device == "cpu" or cpu_requested()


@contextlib.contextmanager
def _child_env(**env: str):
    """Temporarily set env vars so a spawn-child inherits them."""
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@dataclass
class Child:
    name: str
    target: Callable
    args: tuple
    proc: mp.Process
    heartbeat: Any  # mp.Value("d")
    cpu_only: bool
    # Daemonic children die with the supervisor (the default and the right
    # answer for leaf roles). Population members running a NESTED fleet
    # must be non-daemonic — multiprocessing forbids daemonic processes
    # from having children of their own.
    daemon: bool = True
    restarts: int = 0
    started_at: float = 0.0
    # Sliding-window restart budget + backoff state (Supervisor.check):
    restart_times: list = field(default_factory=list)  # respawn timestamps
    streak: int = 0  # consecutive crashes without an intervening healthy window
    respawn_at: float = 0.0  # dead, respawn scheduled at this time (0 = none)
    exhausted: bool = False  # budget blown; fleet shuts down


@dataclass
class Supervisor:
    """Owns the children of one role process; restart-on-silence is the
    feature the reference disabled (``main.py:417-473``). Every child is
    wrapped so a crash writes ``logs/<role>/error_log_<ts>.txt``
    (``utils.errlog``) before the supervisor sees the nonzero exit."""

    ctx: Any = field(default_factory=lambda: mp.get_context("spawn"))
    heartbeat_timeout: float = HEARTBEAT_TIMEOUT
    startup_grace: float = STARTUP_GRACE
    max_restarts: int = 3
    log_root: str = "logs"
    children: list[Child] = field(default_factory=list)
    # Restart budget is per sliding window, not per process lifetime: a
    # child may restart at most `max_restarts` times per trailing
    # `restart_window_s` seconds. Within a crash streak, respawn N is
    # delayed `backoff_s * 2**(N-2)` (first respawn immediate), capped at
    # `backoff_max_s`; a child that stays up a full window resets its
    # streak. This replaces the old lifetime counter + instant respawn,
    # which hot-looped a crashing child straight through its budget.
    restart_window_s: float = RESTART_WINDOW
    backoff_s: float = RESTART_BACKOFF
    backoff_max_s: float = RESTART_BACKOFF_MAX
    poll_s: float = SUPERVISE_POLL
    # Injectable for tests (backoff timing with a mocked clock).
    clock: Callable[[], float] = time.time
    # Optional tpu_rl.chaos.ProcessChaos, polled from loop() — the
    # supervisor is the only place that knows every child's name and pid.
    chaos: Any = None
    # Audit sink for chaos injections (result_dir/chaos.jsonl, the same
    # unified jsonl discipline as rollback/resume/population/autopilot
    # events) so post-hoc run reports can overlay process faults on the
    # recorded curves. None = no audit (best-effort either way).
    audit_dir: str | None = None

    def __post_init__(self):
        self.stop_event = self.ctx.Event()
        self._telem_cfg = None  # (cfg, ip, port) set by enable_telemetry
        self._telem = None  # lazily: (registry, pub, emitter)

    @classmethod
    def from_config(cls, cfg, **kw) -> "Supervisor":
        """Build a supervisor from Config's supervision fields; chaos
        process faults come from ``cfg.chaos_spec`` when set."""
        chaos = kw.pop("chaos", None)
        if chaos is None and getattr(cfg, "chaos_spec", None):
            from tpu_rl.chaos import ProcessChaos

            chaos = ProcessChaos.from_spec(cfg.chaos_spec)
        return cls(
            heartbeat_timeout=cfg.heartbeat_timeout_s,
            startup_grace=cfg.startup_grace_s,
            max_restarts=cfg.max_restarts,
            restart_window_s=cfg.restart_window_s,
            backoff_s=cfg.restart_backoff_s,
            backoff_max_s=cfg.restart_backoff_max_s,
            poll_s=cfg.supervise_poll_s,
            chaos=chaos,
            audit_dir=getattr(cfg, "result_dir", None),
            **kw,
        )

    # ----------------------------------------------------------------- spawn
    def spawn(
        self,
        name: str,
        target: Callable,
        *args,
        cpu_only: bool = True,
        daemon: bool = True,
    ) -> Child:
        from tpu_rl.utils.errlog import role_entry

        if not cpu_only:
            owner = next((c for c in self.children if not c.cpu_only), None)
            if owner is not None:
                # No chip partitioning: a second owner would fail or hang
                # in device init against the first.
                raise RuntimeError(
                    f"{name} would share the accelerator {owner.name} "
                    "already owns (one process per chip); run it on the CPU "
                    "with learner_device='cpu'"
                )
        hb = self.ctx.Value("d", time.time())
        child = Child(
            name=name,
            target=functools.partial(role_entry, target, name, self.log_root),
            args=(*args, self.stop_event, hb),
            proc=None,  # type: ignore[arg-type]
            heartbeat=hb,
            cpu_only=cpu_only,
            daemon=daemon,
        )
        self._start(child)
        self.children.append(child)
        return child

    def _start(self, child: Child) -> None:
        env = {"JAX_PLATFORMS": "cpu"} if child.cpu_only else {}
        with _child_env(**env):
            child.proc = self.ctx.Process(
                target=child.target,
                args=child.args,
                name=child.name,
                daemon=child.daemon,
            )
            child.heartbeat.value = self.clock()
            child.started_at = self.clock()
            child.proc.start()

    # ------------------------------------------------------------- supervise
    def _ensure_dead(self, child: Child) -> None:
        """Terminate, escalating to SIGKILL: SIGTERM stays *pending* on a
        SIGSTOP'd process, so a hung-but-stopped child survives terminate()
        and would wedge its bound ports forever without the escalation."""
        if child.proc.is_alive():
            child.proc.terminate()
            child.proc.join(5)
        if child.proc.is_alive():
            child.proc.kill()
            child.proc.join(5)

    def check(self) -> list[str]:
        """One supervision pass; returns names of children respawned."""
        restarted = []
        now = self.clock()
        for child in self.children:
            if child.exhausted:
                continue
            if child.respawn_at:
                # Dead, waiting out its backoff delay.
                if now >= child.respawn_at:
                    child.respawn_at = 0.0
                    child.restarts += 1
                    self._start(child)
                    restarted.append(child.name)
                continue
            dead = not child.proc.is_alive()
            if dead and child.proc.exitcode == 0:
                continue  # clean exit (e.g. learner hit max_updates): done
            # Silence only counts after the startup grace: jax import + XLA
            # compile block the child's first heartbeat for minutes.
            silent = (
                now - child.heartbeat.value > self.heartbeat_timeout
                and now - child.started_at
                > self.heartbeat_timeout + self.startup_grace
            )
            if not (dead or silent):
                continue
            self._ensure_dead(child)
            if now - child.started_at >= self.restart_window_s:
                child.streak = 0  # it ran healthy for a full window
            child.streak += 1
            child.restart_times = [
                t for t in child.restart_times
                if now - t < self.restart_window_s
            ]
            if len(child.restart_times) >= self.max_restarts:
                child.exhausted = True
                print(
                    f"[supervisor] {child.name}: {len(child.restart_times)} "
                    f"restarts within {self.restart_window_s:.0f}s — budget "
                    "exhausted"
                )
                continue
            child.restart_times.append(now)
            # First crash in a streak respawns immediately (a one-off kill
            # should not cost latency); repeats back off exponentially.
            delay = (
                0.0
                if child.streak <= 1
                else min(
                    self.backoff_s * 2.0 ** (child.streak - 2),
                    self.backoff_max_s,
                )
            )
            if delay > 0:
                child.respawn_at = now + delay
                print(
                    f"[supervisor] {child.name}: crash streak "
                    f"{child.streak}, respawn in {delay:.1f}s"
                )
                continue
            child.restarts += 1
            self._start(child)
            restarted.append(child.name)
        return restarted

    def loop(self, poll: float | None = None) -> None:
        """Block until stop: supervise children, exit when all are gone or
        any child exhausted its restart budget."""
        poll = self.poll_s if poll is None else poll
        while not self.stop_event.is_set():
            if self.chaos is not None:
                for action, name in self.chaos.poll(self.children):
                    print(f"[chaos] {action} -> {name}")
                    from tpu_rl.obs.audit import append_jsonl

                    # Same record shape the autopilot's chaos poll audits,
                    # so report overlays read one schema.
                    append_jsonl(
                        self.audit_dir, "chaos.jsonl",
                        {"ev": "chaos", "action": action, "target": name},
                    )
            restarted = self.check()
            for name in restarted:
                print(f"[supervisor] restarted silent/dead child: {name}")
            self._emit_telemetry()
            if any(
                not c.proc.is_alive() and c.proc.exitcode == 0
                and not c.respawn_at
                for c in self.children
            ):
                # A role completed its bounded work (learner max_updates):
                # wind the whole deployment down.
                self.stop_event.set()
                break
            if any(c.exhausted for c in self.children):
                print("[supervisor] child exhausted restart budget; stopping")
                self.stop_event.set()
                break
            if all(
                not c.proc.is_alive() and not c.respawn_at
                for c in self.children
            ):
                break
            time.sleep(poll)
        self._emit_telemetry(force=True)

    def failures(self) -> list[Child]:
        """Children that exhausted their restart budget or ended by
        themselves with a nonzero exit code — the run did not do what was
        asked. Read after :meth:`stop`; negative codes (killed by our own
        terminate, or by a chaos plan) do not count."""
        return [
            c for c in self.children
            if c.exhausted or (c.proc.exitcode or 0) > 0
        ]

    # ------------------------------------------------------------ telemetry
    def enable_telemetry(self, cfg, stat_ip: str, stat_port: int) -> None:
        """Arm supervisor telemetry (restart/chaos counters shipped onto the
        fleet's stat channel). Idempotent: the first caller wins, so
        local_cluster's three role builders don't triple-publish."""
        if self._telem_cfg is None and cfg.telemetry_enabled:
            self._telem_cfg = (cfg, stat_ip, stat_port)

    def _emit_telemetry(self, force: bool = False) -> None:
        if self._telem_cfg is None:
            return
        if self._telem is None:
            # Lazy build on the first loop() pass: keeps construction off
            # Supervisor.__init__ (tests build bare supervisors) and off
            # import time.
            from tpu_rl.obs import MetricsRegistry, PeriodicSnapshot
            from tpu_rl.runtime.protocol import Protocol
            from tpu_rl.runtime.transport import make_data_pub

            cfg, ip, port = self._telem_cfg
            reg = MetricsRegistry(role="supervisor")
            pub = make_data_pub(cfg, ip, port, bind=False)
            emitter = PeriodicSnapshot(
                reg,
                lambda snap: pub.send(Protocol.Telemetry, snap),
                interval_s=cfg.telemetry_interval_s,
            )
            self._telem = (reg, pub, emitter)
        reg, pub, emitter = self._telem
        reg.counter("supervisor-restarts").set_total(
            sum(c.restarts for c in self.children)
        )
        reg.counter("supervisor-exhausted").set_total(
            sum(1 for c in self.children if c.exhausted)
        )
        reg.gauge("supervisor-children-alive").set(
            sum(1 for c in self.children if c.proc.is_alive())
        )
        if self.chaos is not None:
            reg.counter("chaos-process-kills").set_total(self.chaos.n_kills)
            reg.counter("chaos-process-stops").set_total(self.chaos.n_stops)
        if force:
            emitter.maybe_emit(now=float("inf"))
        else:
            emitter.maybe_emit()

    # ---------------------------------------------------------------- stop
    def stop(self, timeout: float = 10.0) -> None:
        self.stop_event.set()
        deadline = time.time() + timeout
        for c in self.children:
            c.proc.join(max(0.1, deadline - time.time()))
        for c in self.children:
            if c.proc.is_alive():
                c.proc.terminate()
        for c in self.children:
            c.proc.join(2)
            if c.proc.is_alive():
                c.proc.kill()
        if self._telem is not None:
            self._telem[1].close()
            self._telem = None

    def install_signal_handlers(self) -> None:
        """SIGINT/SIGTERM -> cooperative stop (reference ``main.py:493-502``)."""

        def handler(signum, frame):
            self.stop_event.set()

        signal.signal(signal.SIGINT, handler)
        signal.signal(signal.SIGTERM, handler)


# --------------------------------------------------------------------- roles
def learner_role(
    cfg: Config,
    machines: MachinesConfig,
    supervisor: Supervisor | None = None,
    max_updates: int | None = None,
    publish_interval: int = 1,
    seed: int = 0,
) -> Supervisor:
    """Spawn LearnerStorage + LearnerService sharing shm (reference
    ``learner_sub_process``, ``main.py:301-414``)."""
    from tpu_rl.runtime.learner_service import learner_main
    from tpu_rl.runtime.storage import storage_main

    sup = supervisor or Supervisor.from_config(cfg)
    # Supervisor restart/chaos counters ride the stat channel the storage
    # child SUB-binds on this host (same path as the learner's snapshots).
    sup.enable_telemetry(cfg, "127.0.0.1", machines.learner_port)
    layout = BatchLayout.from_config(cfg)
    from tpu_rl.config import is_off_policy

    off_policy = is_off_policy(cfg.algo)
    capacity = cfg.buffer_size if off_policy else cfg.batch_size
    handles = alloc_handles(
        layout, capacity, ctx=sup.ctx, generations=1 if off_policy else 2
    )
    stat_array = sup.ctx.Array("f", STAT_SLOTS, lock=False)

    sup.spawn(
        "storage", storage_main, cfg, handles, machines.learner_port, stat_array
    )
    # Inference fleet port plan (collision-checked): replica 0 lives inside
    # the learner process (zero-staleness swaps); replicas 1..N-1 are
    # supervised children below.
    inference_ports = (
        machines.inference_ports(cfg) if cfg.act_mode == "remote" else None
    )
    sup.spawn(
        "learner",
        functools.partial(
            learner_main,
            max_updates=max_updates,
            publish_interval=publish_interval,
            seed=seed,
            # The centralized-inference ROUTER (act_mode="remote") binds in
            # the learner process; the service itself gates on act_mode.
            inference_port=(
                inference_ports[0] if inference_ports is not None else None
            ),
            # The stat channel storage SUB-binds: the learner's Telemetry
            # snapshots ship there (LearnerService gates on telemetry_enabled).
            stat_port=machines.learner_port,
        ),
        cfg,
        handles,
        machines.model_port,
        stat_array,
        # "auto": the learner owns the accelerator. "cpu": pin it to the
        # CPU (CI, or when another process holds the chip).
        cpu_only=owner_on_cpu(cfg),
    )
    if inference_ports is not None and cfg.inference_replicas > 1:
        from tpu_rl.fleet import replica_main

        for i in range(1, cfg.inference_replicas):
            # Child names follow the chaos plane's prefix convention:
            # ``kill:inference-1@t+8s`` targets exactly these processes.
            sup.spawn(
                f"inference-{i}",
                functools.partial(replica_main, seed=seed),
                cfg,
                i,
                inference_ports[i],
                "127.0.0.1",  # learner (model PUB) is on this host
                machines.model_port,
                machines.learner_port,
                # The learner owns the chip (and serves replica 0 on it);
                # the extra replicas act on the CPU.
                cpu_only=True,
            )
    return sup


def worker_role(
    cfg: Config,
    machines: MachinesConfig,
    machine_idx: int = 0,
    supervisor: Supervisor | None = None,
    seed: int = 0,
) -> Supervisor:
    """Spawn num_p actor processes (reference ``worker_sub_process``,
    ``main.py:244-299``)."""
    from tpu_rl.runtime.worker import worker_main

    sup = supervisor or Supervisor.from_config(cfg)
    sup.enable_telemetry(cfg, machines.learner_ip, machines.learner_port)
    m = machines.workers[machine_idx]
    for i in range(m.num_p):
        sup.spawn(
            f"worker-{machine_idx}-{i}",
            functools.partial(
                worker_main,
                seed=seed * 1000 + machine_idx * 100 + i,
                # A fleet (N > 1) hands workers the full endpoint list so
                # FleetClient can balance/hedge; a single service keeps the
                # scalar port and the plain InferenceClient.
                inference_port=(
                    None if cfg.act_mode != "remote"
                    else machines.inference_ports(cfg)
                    if cfg.inference_replicas > 1
                    else machines.inference_port
                ),
            ),
            cfg,
            i,
            m.manager_ip,
            m.port,
            machines.learner_ip,
            machines.model_port,
        )
    return sup


def manager_role(
    cfg: Config,
    machines: MachinesConfig,
    machine_idx: int = 0,
    supervisor: Supervisor | None = None,
) -> Supervisor:
    """Spawn the relay (reference ``manager_sub_process``, ``main.py:228-242``)."""
    from tpu_rl.runtime.manager import manager_main

    sup = supervisor or Supervisor.from_config(cfg)
    sup.enable_telemetry(cfg, machines.learner_ip, machines.learner_port)
    m = machines.workers[machine_idx]
    sup.spawn(
        f"manager-{machine_idx}",
        manager_main,
        cfg,
        m.port,
        machines.learner_ip,
        machines.learner_port,
    )
    return sup


def colocated_role(
    cfg: Config,
    machines: MachinesConfig | None = None,
    supervisor: Supervisor | None = None,
    max_updates: int | None = None,
    seed: int = 0,
) -> Supervisor:
    """Spawn the colocated-mode loop (``runtime/colocated.py``): envs live
    on the accelerator inside the jitted train program, so this host's
    whole deployment is ONE supervised child — no storage, manager or
    workers. Routing: ``cfg.sebulba_split > 0`` spawns the split
    actor/learner-group loop (``runtime/sebulba.py``), otherwise the fused
    Anakin program; ``cfg.multihost`` is honored either way — the child
    joins the jax.distributed runtime exactly like the learner role, one
    ``colocated_role`` invocation per pod host. ``machines`` is accepted
    (and ignored) so the CLI can dispatch every role through one
    signature."""
    del machines  # colocated mode has no fleet topology
    if cfg.sebulba_split > 0:
        from tpu_rl.runtime.sebulba import sebulba_main as child_main
    else:
        from tpu_rl.runtime.colocated import colocated_main as child_main

    sup = supervisor or Supervisor.from_config(cfg)
    sup.spawn(
        "colocated",
        functools.partial(child_main, max_updates=max_updates, seed=seed),
        cfg,
        # "auto": the fused program owns the accelerator. "cpu": pin it to
        # the CPU (CI, or when another process holds the chip).
        cpu_only=owner_on_cpu(cfg),
    )
    return sup


def local_cluster(
    cfg: Config,
    machines: MachinesConfig | None = None,
    max_updates: int | None = None,
    publish_interval: int = 1,
    seed: int = 0,
) -> Supervisor:
    """Everything on one host: learner + storage + manager + workers under a
    single supervisor. The smallest real deployment and the integration-test
    harness. In colocated mode the "cluster" collapses to the single fused
    child (``colocated_role``) — same entry point, same supervisor contract."""
    machines = machines or MachinesConfig()
    sup = Supervisor.from_config(cfg)
    if cfg.env_mode == "colocated":
        return colocated_role(
            cfg, machines, supervisor=sup, max_updates=max_updates, seed=seed
        )
    learner_role(
        cfg,
        machines,
        supervisor=sup,
        max_updates=max_updates,
        publish_interval=publish_interval,
        seed=seed,
    )
    manager_role(cfg, machines, supervisor=sup)
    worker_role(cfg, machines, supervisor=sup, seed=seed)
    return sup


def population_role(
    cfg: Config,
    machines: MachinesConfig | None = None,
    max_updates: int | None = None,
):
    """Build the PBT controller (``population/controller.py``). Unlike the
    other roles this returns the controller, not a Supervisor: the
    controller IS the orchestrator and runs in the calling process, owning
    its own supervisor whose children are the K ``member-<k>`` runs."""
    from tpu_rl.population import PopulationController

    return PopulationController(cfg, machines=machines, max_updates=max_updates)


def autopilot_role(
    cfg: Config,
    machines: MachinesConfig | None = None,
    manage_all: bool = False,
    seed: int = 0,
):
    """Build the fleet autopilot (``autopilot/controller.py``). Same
    controller-as-orchestrator shape as ``population_role``: the returned
    controller runs in the calling process and owns its own supervisor,
    whose children are the elastic ``inference-<i>`` replicas (and any
    autopilot-managed workers) it scales in response to the fleet's SLO
    burn rates, goodput and straggler scores."""
    from tpu_rl.autopilot import AutopilotController

    return AutopilotController(
        cfg, machines=machines, manage_all=manage_all, seed=seed
    )
