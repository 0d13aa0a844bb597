"""CLI: ``python -m tpu_rl <role> [options]``.

Replaces the reference's argv dispatch (``/root/reference/main.py:475-529``)
with argparse. Roles mirror the reference's ``*_sub_process`` entry points
plus ``local`` (whole cluster on one host — the smallest real deployment).

Examples:
    python -m tpu_rl local --env CartPole-v1 --algo PPO
    python -m tpu_rl local --env CartPole-v1 --algo PPO --env-mode colocated
    python -m tpu_rl learner --params params.json --machines machines.json
    python -m tpu_rl manager --machines machines.json --machine-idx 0
    python -m tpu_rl worker  --machines machines.json --machine-idx 0
"""

from __future__ import annotations

import argparse
import os
import sys

from tpu_rl.config import Config, MachinesConfig, default_result_dirs


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="tpu_rl")
    p.add_argument(
        "role",
        choices=[
            "local", "learner", "manager", "worker", "population", "autopilot",
        ],
        help="which role this host runs ('population' = PBT controller "
        "orchestrating K member runs; 'autopilot' = closed-loop autoscaler "
        "driving the elastic inference fleet from SLO burn rates, goodput "
        "and straggler scores; see tpu_rl.population / tpu_rl.autopilot)",
    )
    p.add_argument("--params", help="parameters.json-shaped config file")
    p.add_argument("--machines", help="machines.json-shaped topology file")
    p.add_argument("--machine-idx", type=int, default=0,
                   help="index into machines.workers for manager/worker roles")
    p.add_argument("--env", help="override env id")
    p.add_argument("--algo", help="override algorithm")
    p.add_argument("--env-mode", choices=["distributed", "colocated"],
                   default=None,
                   help="'colocated' fuses act->env.step->train into one "
                   "jitted on-device program (jittable envs only; see "
                   "tpu_rl/envs)")
    p.add_argument("--colocated-envs", type=int, default=None,
                   help="env-batch size for colocated mode (overrides "
                   "batch_size there; 0/unset = batch_size)")
    p.add_argument("--sebulba-split", type=int, default=None,
                   help="colocated mode: dedicate this many local devices "
                   "to the rollout program (actor group); the rest run "
                   "train_step, fed through a bounded on-device queue "
                   "(Podracer Sebulba). 0/unset = fused Anakin")
    p.add_argument("--sebulba-queue", type=int, default=None,
                   help="bounded device-resident batch slots between the "
                   "sebulba device groups (2 = double buffering)")
    p.add_argument("--mesh-data", type=int, help="learner data-mesh size")
    p.add_argument("--act-mode", choices=["local", "remote"], default=None,
                   help="'remote' routes worker acting through the "
                   "centralized inference service/fleet (SEED-style); "
                   "'local' acts on worker host cores (default: local)")
    p.add_argument("--inference-replicas", type=int, default=None,
                   help="inference fleet size for act_mode=remote: replica 0 "
                   "serves in-process in the learner, replicas 1..N-1 are "
                   "supervised children fed by the model broadcast "
                   "(default 1 = the single in-learner service)")
    p.add_argument("--inference-base-port", type=int, default=None,
                   help="first port of the fleet's consecutive replica port "
                   "range, collision-checked against the learner/model/"
                   "telemetry/manager ports (0/unset = learner_port + 2)")
    p.add_argument("--inference-hedge-ms", type=int, default=None,
                   help="resend an unanswered inference request to a second "
                   "replica after this many ms (0/unset = hedge only at the "
                   "full timeout boundary — plain failover)")
    p.add_argument("--inference-mesh-data", type=int, default=None,
                   help="GSPMD data-mesh size each inference replica shards "
                   "its act batch over (1/unset = single-device)")
    p.add_argument("--inference-dtype", choices=["f32", "bf16", "int8"],
                   default=None,
                   help="serving-param precision: bf16 halves / int8 "
                   "quarters the resident actor tree; the jitted act step "
                   "dequantizes, so compute stays f32 (default: f32)")
    p.add_argument("--inference-buckets", type=int, default=None,
                   help="smallest bucket of the power-of-two flush-shape "
                   "ladder, all compiled before the socket binds; 0/unset = "
                   "one padded program (the bit-for-bit legacy path)")
    p.add_argument("--act-kernel", choices=["xla", "pallas"], default=None,
                   help="'pallas' fuses the act step into one VMEM-resident "
                   "TPU kernel where it fits, falling back to XLA elsewhere "
                   "(default: xla)")
    p.add_argument("--max-updates", type=int, default=None)
    p.add_argument("--publish-interval", type=int, default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no-result-dir", action="store_true",
                   help="disable tensorboard/checkpoint output")
    p.add_argument("--result-dir", default=None,
                   help="fixed result dir (checkpoints land in "
                   "<result-dir>/models). Run the same role twice with the "
                   "same --result-dir and the learner resumes from the "
                   "newest committed checkpoint instead of starting over "
                   "(default: a fresh timestamped dir per run)")
    p.add_argument("--model-save-interval", type=int, default=None,
                   help="checkpoint every N learner updates")
    p.add_argument("--ckpt-keep", type=int, default=None,
                   help="committed checkpoints retained on disk (>= 1)")
    p.add_argument("--ckpt-sync", action="store_true",
                   help="blocking checkpoint saves on the update loop "
                   "(default: async background writer; both are "
                   "commit-atomic — this is the A/B baseline)")
    p.add_argument("--resume-force", action="store_true",
                   help="resume even if the checkpoint's config fingerprint "
                   "(model/env structure) disagrees with the current config")
    p.add_argument("--telemetry-port", type=int, default=None,
                   help="serve Prometheus /metrics + /healthz from the "
                   "storage process on this port (0/unset = off)")
    p.add_argument("--history-dir", default=None,
                   help="run-history time-series store location (unset = "
                   "result_dir/history; the store exists only while the "
                   "telemetry plane is on)")
    p.add_argument("--history-chunk-s", type=float, default=None,
                   help="history store chunk rotation period in seconds "
                   "(default 60)")
    p.add_argument("--history-retention-s", type=float, default=None,
                   help="history store retention horizon in seconds — older "
                   "chunks are GC'd at rotation (default 3600)")
    p.add_argument("--no-learn-diag", action="store_true",
                   help="disable the learning-dynamics plane (in-jit "
                   "entropy/KL/ESS/clip diagnostics, staleness-conditioned "
                   "learner-diag-* gauges, result_dir/learn.jsonl); on by "
                   "default — readback rides the loss-log cadence, so the "
                   "steady-state cost is one extra fused device program")
    p.add_argument("--watchdog-diag", action="store_true",
                   help="feed approx-KL and negated ESS from the "
                   "learning-dynamics plane into the divergence watchdog's "
                   "z-score channels (requires the watchdog and learn-diag "
                   "both on)")
    p.add_argument("--trace-sample-n", type=int, default=None,
                   help="sample every Nth worker tick into the fleet trace "
                   "(result_dir/fleet_trace.json); 0/unset = off")
    p.add_argument("--transport", choices=["tcp", "shm", "auto"],
                   default=None,
                   help="data-hop fabric for the rollout/stat fan-in: 'shm' "
                   "routes same-host manager->storage and learner->storage "
                   "hops through shared-memory rings (no sockets), 'auto' "
                   "picks shm only when the peer address is loopback "
                   "(default: tcp)")
    p.add_argument("--slo-spec", default=None,
                   help="declarative SLO rules evaluated live, e.g. "
                   "'p99:inference-rtt<5ms@window=30s,gauge:learner-mfu"
                   ">0.002,rate:transport-rejected-frames<1/s' "
                   "(see tpu_rl.obs.slo; served at /slo)")
    p.add_argument("--slo-fail-run", action="store_true",
                   help="exit nonzero (storage child) when the final SLO "
                   "verdict has a hard-failing rule")
    p.add_argument("--chaos-spec", default=None,
                   help="deterministic fault plan, e.g. "
                   "'kill:worker-0-1@t+3s,corrupt:rollout@p=0.01,"
                   "delay:manager@50ms' (see tpu_rl.chaos.plan)")
    p.add_argument("--chaos-seed", type=int, default=None,
                   help="seed for the chaos plane's per-site RNG streams")
    p.add_argument("--pop-spec", default=None,
                   help="PBT search-space grammar for the population role, "
                   "e.g. 'lr:log[1e-4,1e-2] entropy_coef:lin[0,0.05] "
                   "perturb=1.2,0.8 interval=200u k=4' "
                   "(see tpu_rl.population.spec)")
    p.add_argument("--pop-seed", type=int, default=None,
                   help="seed for population sampling/mutation/selection "
                   "(deterministic per-member streams)")
    p.add_argument("--autopilot-spec", default=None,
                   help="closed-loop autoscaling rules for the autopilot "
                   "role, e.g. 'scale_out:replicas?burn:inference-rtt>0.5"
                   "@sustain=3@cooldown=10s@max=4,scale_in:replicas?burn:"
                   "inference-rtt<0.05@min=1,limit=6/60s' "
                   "(see tpu_rl.autopilot.policy)")
    p.add_argument("--autopilot-poll", type=float, default=None,
                   help="seconds between autopilot control ticks "
                   "(scrape -> decide -> actuate)")
    p.add_argument("--autopilot-manage-all", action="store_true",
                   help="autopilot owns the whole replica range from index "
                   "0 (standalone fleets); default: the statically "
                   "provisioned learner-owned replicas stay untouched and "
                   "the autopilot manages only the elastic tail")
    p.add_argument("--heartbeat-timeout", type=float, default=None,
                   help="seconds of child-heartbeat silence before the "
                   "supervisor declares it hung and restarts it")
    p.add_argument("--startup-grace", type=float, default=None,
                   help="seconds after spawn before silence counts "
                   "(covers jit compile / env build)")
    p.add_argument("--supervise-poll", type=float, default=None,
                   help="supervisor health-check interval in seconds")
    p.add_argument("--max-restarts", type=int, default=None,
                   help="restarts allowed per child within restart_window_s "
                   "before the fleet shuts down")
    return p


def load_config(args: argparse.Namespace) -> tuple[Config, MachinesConfig]:
    cfg = Config.from_json(args.params) if args.params else Config()
    overrides = {}
    if args.env:
        overrides["env"] = args.env
    if args.algo:
        overrides["algo"] = args.algo
    if args.env_mode is not None:
        overrides["env_mode"] = args.env_mode
    if args.colocated_envs is not None:
        overrides["colocated_envs"] = args.colocated_envs
    if args.sebulba_split is not None:
        overrides["sebulba_split"] = args.sebulba_split
    if args.sebulba_queue is not None:
        overrides["sebulba_queue"] = args.sebulba_queue
    if args.mesh_data:
        overrides["mesh_data"] = args.mesh_data
    if args.act_mode is not None:
        overrides["act_mode"] = args.act_mode
    if args.inference_replicas is not None:
        overrides["inference_replicas"] = args.inference_replicas
    if args.inference_base_port is not None:
        overrides["inference_base_port"] = args.inference_base_port
    if args.inference_hedge_ms is not None:
        overrides["inference_hedge_ms"] = args.inference_hedge_ms
    if args.inference_mesh_data is not None:
        overrides["inference_mesh_data"] = args.inference_mesh_data
    if args.inference_dtype is not None:
        overrides["inference_dtype"] = args.inference_dtype
    if args.inference_buckets is not None:
        overrides["inference_buckets"] = args.inference_buckets
    if args.act_kernel is not None:
        overrides["act_kernel"] = args.act_kernel
    if args.telemetry_port is not None:
        overrides["telemetry_port"] = args.telemetry_port
    if args.history_dir is not None:
        overrides["history_dir"] = args.history_dir
    if args.history_chunk_s is not None:
        overrides["history_chunk_s"] = args.history_chunk_s
    if args.history_retention_s is not None:
        overrides["history_retention_s"] = args.history_retention_s
    if args.no_learn_diag:
        overrides["learn_diag"] = False
    if args.watchdog_diag:
        overrides["watchdog_diag"] = True
    if args.trace_sample_n is not None:
        overrides["trace_sample_n"] = args.trace_sample_n
    if args.transport is not None:
        overrides["transport"] = args.transport
    if args.slo_spec is not None:
        overrides["slo_spec"] = args.slo_spec
    if args.slo_fail_run:
        overrides["slo_fail_run"] = True
    if args.chaos_spec is not None:
        overrides["chaos_spec"] = args.chaos_spec
    if args.pop_spec is not None:
        overrides["pop_spec"] = args.pop_spec
    if args.pop_seed is not None:
        overrides["pop_seed"] = args.pop_seed
    if args.autopilot_spec is not None:
        overrides["autopilot_spec"] = args.autopilot_spec
    if args.autopilot_poll is not None:
        overrides["autopilot_poll_s"] = args.autopilot_poll
    if args.chaos_seed is not None:
        overrides["chaos_seed"] = args.chaos_seed
    if args.heartbeat_timeout is not None:
        overrides["heartbeat_timeout_s"] = args.heartbeat_timeout
    if args.startup_grace is not None:
        overrides["startup_grace_s"] = args.startup_grace
    if args.supervise_poll is not None:
        overrides["supervise_poll_s"] = args.supervise_poll
    if args.max_restarts is not None:
        overrides["max_restarts"] = args.max_restarts
    if args.result_dir is not None:
        overrides["result_dir"] = args.result_dir
        # A user-set model_dir (e.g. from --params) still wins; otherwise
        # checkpoints live under the pinned result dir so a rerun with the
        # same flag resumes from them.
        if cfg.model_dir is None:
            overrides["model_dir"] = os.path.join(args.result_dir, "models")
    if args.model_save_interval is not None:
        overrides["model_save_interval"] = args.model_save_interval
    if args.ckpt_keep is not None:
        overrides["ckpt_keep"] = args.ckpt_keep
    if args.ckpt_sync:
        overrides["ckpt_async"] = False
    if args.resume_force:
        overrides["resume_force"] = True
    if overrides:
        cfg = cfg.replace(**overrides)
    machines = (
        MachinesConfig.from_json(args.machines)
        if args.machines
        else MachinesConfig()
    )
    return cfg, machines


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    cfg, machines = load_config(args)

    # Probe env spaces once, in the parent (reference ``main.py:82-95``).
    from tpu_rl.runtime.env import probe_spaces

    cfg = probe_spaces(cfg)
    if not args.no_result_dir and (
        cfg.result_dir is None or cfg.model_dir is None
    ):
        result_dir, model_dir = default_result_dirs()
        # Fill only the unset dirs — a user-configured model_dir (checkpoint
        # resume target) must never be clobbered by the timestamped default.
        cfg = cfg.replace(
            result_dir=cfg.result_dir or result_dir,
            model_dir=cfg.model_dir or model_dir,
        )

    from tpu_rl.runtime import runner

    if args.role == "population":
        # The controller IS the orchestrator: it runs in this process and
        # drives its own supervisor (members are the children), so it does
        # not go through the sup.loop() path below.
        ctrl = runner.population_role(
            cfg, machines, max_updates=args.max_updates
        )
        ctrl.install_signal_handlers()
        doc = ctrl.run()
        return 0 if doc.get("ok") else 1
    if args.role == "autopilot":
        # Same controller-as-orchestrator shape as the population role.
        ctrl = runner.autopilot_role(
            cfg, machines, manage_all=args.autopilot_manage_all,
            seed=args.seed,
        )
        ctrl.install_signal_handlers()
        doc = ctrl.run()
        return 0 if doc.get("ok") else 1
    if cfg.env_mode == "colocated" and args.role in ("manager", "worker"):
        print(
            f"colocated mode has no {args.role} role: the envs live inside "
            "the fused on-device program (use 'local' or 'learner')",
            file=sys.stderr,
        )
        return 2
    if cfg.env_mode == "colocated" and args.role == "learner":
        sup = runner.colocated_role(
            cfg, machines, max_updates=args.max_updates, seed=args.seed
        )
    elif args.role == "local":
        sup = runner.local_cluster(
            cfg,
            machines,
            max_updates=args.max_updates,
            publish_interval=args.publish_interval,
            seed=args.seed,
        )
    elif args.role == "learner":
        sup = runner.learner_role(
            cfg,
            machines,
            max_updates=args.max_updates,
            publish_interval=args.publish_interval,
            seed=args.seed,
        )
    elif args.role == "manager":
        sup = runner.manager_role(cfg, machines, machine_idx=args.machine_idx)
    else:
        sup = runner.worker_role(
            cfg, machines, machine_idx=args.machine_idx, seed=args.seed
        )

    sup.install_signal_handlers()
    try:
        sup.loop()
    finally:
        sup.stop()
    failures = sup.failures()
    for c in failures:
        print(
            f"[supervisor] {c.name} failed (exit code {c.proc.exitcode}, "
            f"restart budget {'exhausted' if c.exhausted else 'left'})",
            file=sys.stderr,
        )
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
