# Developer/CI entry points. `make ci` is what the workflow runs.

PY ?= python

.PHONY: lint format-check analyze typecheck test native-build protocol-matrix \
	obs-smoke trace-smoke chaos-smoke colocated-smoke \
	resume-smoke slo-smoke loadgen-smoke serving-smoke heal-smoke \
	pbt-smoke goodput-smoke autopilot-smoke sebulba-smoke history-smoke ci

lint:
	ruff check .

format-check:
	ruff format --check .

# Repo-native static analysis plane (tools/analysis): hot-path purity,
# jit-boundary hygiene, protocol/mailbox consistency, metric/config drift,
# thread discipline. Exit 0 = clean (waivers live in tools/analysis/baseline.toml).
analyze:
	$(PY) -m tools.analysis

# mypy --strict over the protocol-critical core (wire format, mailbox, shm
# rings). Skips gracefully where mypy isn't installed — CI always runs it.
typecheck:
	@if $(PY) -c "import mypy" >/dev/null 2>&1; then \
		$(PY) -m mypy tpu_rl/runtime/protocol.py tpu_rl/runtime/mailbox.py \
			tpu_rl/runtime/transport.py; \
	else \
		echo "mypy not installed; skipping typecheck (CI runs it)"; \
	fi

# Tier-1 suite: the fast CPU gate (slow-marked cluster/e2e tests excluded).
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# Build (and cache) the native codec from source, then prove it loaded —
# CI must never silently fall back to the zlib/Python path.
native-build:
	JAX_PLATFORMS=cpu $(PY) -c "from tpu_rl.runtime import native; \
		assert native.available(), 'native codec failed to build'; \
		print('native codec OK:', native.LIB._name)"

# Wire-protocol + relay + chaos suites twice: once with the native codec
# force-disabled (TPU_RL_NATIVE=0 exercises the pure-Python fallback every
# deployment without a toolchain runs) and once against the freshly built
# library — both paths must hold the same contracts.
protocol-matrix: native-build
	JAX_PLATFORMS=cpu TPU_RL_NATIVE=0 $(PY) -m pytest -q \
		tests/test_protocol.py tests/test_relay_raw.py \
		tests/test_relay_units.py tests/test_native_validate.py \
		tests/test_shm_transport.py tests/test_chaos.py \
		-p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) -m pytest -q \
		tests/test_protocol.py tests/test_relay_raw.py \
		tests/test_relay_units.py tests/test_native_validate.py \
		tests/test_shm_transport.py tests/test_chaos.py \
		-p no:cacheprovider

# Telemetry-plane smoke: boot the smallest real cluster with the plane on,
# scrape /metrics + /healthz mid-run, validate telemetry.json + trace.json.
obs-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/obs_smoke.py

# Distributed-tracing smoke: cluster run with rollout lineage sampling on,
# then validate the merged fleet_trace.json — all four roles on one
# clock-corrected timeline, >=1 worker->manager->storage->learner flow chain.
trace-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/trace_smoke.py

# Chaos smoke: run the cluster under a deterministic fault plan (worker
# kill + rollout corruption + relay delay) and assert the run completes,
# >=1 supervised restart happened, and injected corruptions == fleet
# rejected frames (exact fault accounting).
chaos-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/chaos_smoke.py

# Colocated (Anakin) smoke: a short fused on-device CartPole run must learn
# (best-window mean return over the bar).
colocated-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/colocated_smoke.py

# Resume smoke: SIGKILL the learner and storage after the first committed
# checkpoint and assert supervised respawn, monotonic resume from the newest
# committed index at a bumped run epoch, stale-epoch frames fenced, workers
# re-registered, fault accounting intact, and a planted torn save never
# restored.
resume-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/resume_smoke.py

# SLO-plane smoke: the same small cluster twice under Config.slo_spec — a
# meetable six-rule spec (system health + learner-diag training health)
# must scrape green on /slo and exit 0; adding an impossible rule with
# slo_fail_run armed must scrape 503 and exit nonzero.
slo-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/slo_smoke.py

# Load-plane smoke: a real two-replica inference fleet under a >=10k-client
# open-loop sweep with a SIGKILL of replica 1 mid-sweep — asserts >=99.9%
# success via hedged failover, a green sub-saturation p99:inference-rtt
# verdict, and a monotonic version floor (curve at <tmp>/loadgen.json).
loadgen-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/loadgen_smoke.py

# Serving fast-path smoke: a two-replica fleet serving bf16-quantized
# params through the bucket ladder [8, 16] — mixed-width sweep with zero
# client failures, live replica counters holding inference-xla-recompiles
# at exactly 0 post-warm, and a live parity spot-check of the quantized
# reply logits against the local f32 reference act.
serving-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/serving_smoke.py

# Self-healing smoke: in-jit guard bit-identity + NaN containment, then a
# NaN/spike data-chaos cluster run — >=1 watchdog rollback to a committed
# checkpoint with an epoch fence, the poisoned worker quarantined and later
# cleared, exact injected==poisoned accounting — then a clean run where the
# armed healing plane changes nothing.
heal-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/heal_smoke.py

# Population smoke: K=4 colocated CartPole variants under the PBT
# controller, one poisoned (lr ~100x) — assert the poisoned variant is
# truncation-replaced (winner checkpoint adopted + hyperparameters
# mutated), a SIGKILL mid-exploit leaves the member resumable, and the
# final leaderboard's best fitness clears the CartPole bar.
pbt-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/pbt_smoke.py

# Goodput-plane smoke: 3-worker cluster with the wall-clock ledger on —
# every role's bucket ratios sum to 1 within 1% (overcommit <= 1%), all
# roles show nonzero goodput, gauge:learner-goodput-ratio>0.0 evaluates
# green on /slo, a SIGSTOP'd worker surfaces as the top straggler on
# /goodput, and `python -m tpu_rl.obs.top --once` renders a live frame.
goodput-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/goodput_smoke.py

autopilot-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/autopilot_smoke.py

# Pod-scale colocated smoke (ISSUE 18): 2 virtual hosts train the fused
# pod-Anakin CartPole to the learning bar with a SIGKILL + rejoin (epoch
# bump, newest-committed resume, final checkpoint readable), then the
# sebulba split proves actor/learner overlap through the bounded queue.
sebulba-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/sebulba_smoke.py

# Run-history smoke (ISSUE 20): chaos-kill cluster run with the history
# plane on — /query shows run progress, the report renders the chaos
# event overlay, self-compare is green, and doctored candidates (dropped
# channel / 20x slower) gate red.
history-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/history_smoke.py

ci: lint analyze typecheck test protocol-matrix obs-smoke \
	trace-smoke chaos-smoke colocated-smoke resume-smoke slo-smoke \
	loadgen-smoke serving-smoke heal-smoke pbt-smoke goodput-smoke \
	autopilot-smoke sebulba-smoke history-smoke
