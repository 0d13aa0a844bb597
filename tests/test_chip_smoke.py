"""chip_smoke.py's contract off the chip: without a TPU it exits nonzero at
once, names the reason and prints no result; its phase bodies take their
sizes as arguments and pass tiny on the CPU with the kernels interpreted."""

import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(cwd, script, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    return subprocess.run(
        [sys.executable, script], capture_output=True, text=True, timeout=300,
        cwd=cwd, env={**base, **env},
    )


def test_explicit_cpu_fails_at_once_naming_the_reason():
    r = _run(REPO, SCRIPT, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "JAX_PLATFORMS=cpu" in r.stderr and "needs a TPU" in r.stderr
    assert r.stdout == ""  # no result line


def test_no_accelerator_found_fails_without_a_result():
    """JAX_PLATFORMS unset and no chip: JAX's own fallback would hand the
    probe the CPU — the script refuses it instead of degrading."""
    r = _run(REPO, SCRIPT)
    assert r.returncode != 0
    assert "found no accelerator" in r.stderr
    assert r.stdout == ""


def test_alone_in_a_directory_fails_without_a_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    assert "tpu_rl" in r.stderr
    assert r.stdout == ""


def test_kernel_checks_pass_tiny_interpreted():
    rows = chip_smoke.kernel_checks(
        lstm_shapes=((8, 3, 16, "auto"), (16, 3, 16, "force")),
        act_shapes=((8, 4, 16, 2),),
        flash_shapes=(
            (2, 128, 2, 2, 16, None, True, None),
            (1, 128, 2, 2, 16, None, False, None),
            (2, 128, 4, 2, 16, 1.0 / 64, True, None),
            (1, 128, 4, 2, 16, 0.25, True, 48),
        ),
        ssd_shapes=((2, 32, 4, 8, 1, 16, 8), (2, 32, 4, 8, 2, 16, 8)),
        gmm_shapes=((512, 4, 64, 48),),
        moe_shapes=((300, 3, 4, 16, 64, 48, 0.0, False), (300, 3, 4, 16, 64, 48, 0.75, False),
                    (300, 3, 4, 16, 64, 48, 0.0, True), (300, 3, 4, 16, 64, 48, 0.75, True),
                    (300, 3, 4, 16, 64, 48, 1.0, True)),
        row_add_shapes=((512, 300, 128, 4),),
        seam_shapes=((512, 4, 2, 16, 0.25, 160, 128, 128), (512, 4, 2, 16, 0.25, None, 128, 128)),
        attn_bwd_shapes=((512, 4, 2, 16, 0.25, None, 128, 128), (512, 4, 2, 16, 0.25, 160, 128, 128),
                         (512, 2, 2, 32, 32**-0.5, None, 128, 128)),
        qwen3_next_shapes=(("linear", 2, 96), ("attention", 2, 128)),
        qwen3_next_widths=dict(
            hidden_size=64, rms_norm_eps=1e-6, linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16, linear_conv_kernel_dim=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32, rope_theta=10000000,
            partial_rotary_factor=0.25),
        gdn_shapes=((2, 40, 2, 4, 16, 16, 8),),
        glm_shapes=(("mixer", 2, 128), ("step", 2, 128)),
        glm_widths=dict(
            hidden_size=64, rms_norm_eps=1e-5, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
            rope_theta=1000000),
        lfm2_shapes=(("mixer", 2, 128), ("step", 2, 128)),
        lfm2_widths=dict(hidden_size=64, conv_L_cache=3),
        lfm2_attn_bwd_shapes=((512, 8, 2, 16, 0.25, None, 128, 128),),
        evabyte_shapes=(("mixer", 2, 128), ("pool", 2, 128), ("step", 2, 128),
                        ("pool-seams", 2, 128)),
        evabyte_widths=dict(hidden_size=64, num_attention_heads=4, window_size=32, chunk_size=4,
                            rope_theta=100000, init_std=0.05),
        ling_flash_shapes=(("kda-seams", 2, 64), ("kda-bound", 2, 64), ("kda-one-episode", 2, 64),
                           ("mla", 2, 128), ("route", 2, 128)),
        ling_flash_widths=dict(
            hidden_size=64, rms_norm_eps=1e-6, num_attention_heads=4, head_dim=16,
            q_lora_rank=None, kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, rope_theta=6000000, short_conv_kernel_size=4, kda_lower_bound=-5,
            num_experts=32, num_experts_per_tok=6, n_group=8, topk_group=3,
            routed_scaling_factor=2.5),
        ling_flash_chunk=16,
        interpret=True,
    )
    assert len(rows) == 38
    # ling_flash's five rows come last: the per-channel scan's Pallas pair three times, latent
    # attention at 24-wide queries and 16-wide values, the group-limited choice (exact)
    route, mla, *scans = [rows.pop() for _ in range(5)]
    assert route["kernel"].startswith("ling_flash group-limited route N256/E32/k6/groups 3 of 8")
    assert route["ok"] and route["err"] < 1e-6 and route["tol"] == 1e-5  # an id apart reads 3e-2
    assert mla["kernel"].startswith("ling_flash mla mixer fwd+bwd B2/T128 bf16 at 4 heads of 24:16")
    assert mla["ok"] and mla["err"] > 0
    for scan, what in zip(scans, ("one-episode: 0 seams", "bound: ", "seams: ")):
        assert scan["kernel"].startswith("kda fwd+bwd B2/T64/H4x16/Q16 bf16 (" + what), scan
        assert scan["ok"] and 0 < scan["err"] <= scan["tol"], scan
    # the first row timed: the Pallas pair (here the interpreter's), the recurrence, the body
    assert all(scans[2][k] > 0 for k in ("ms", "ms_ref", "ms_jnp")) and "ms" not in scans[0]
    assert "mean decay a step 0.007" in scans[1]["kernel"]  # e^-5
    # evabyte's four rows come last: the mixer, the pooling alone (timed), the acting
    # form, the pooling on a window with more seams and several absent candidates
    seams, step, pool, mixer = rows.pop(), rows.pop(), rows.pop(), rows.pop()
    assert mixer["kernel"].startswith("evabyte eva mixer fwd+bwd B2/T128 bf16 vs the plain reference")
    assert pool["kernel"].startswith("evabyte eva pool fwd+bwd B2/T128/H4x16/C4 bf16")
    assert step["kernel"].startswith("evabyte step B2/T128 bf16 over a ring of 32 and 32 summaries")
    assert mixer["ok"] and pool["ok"] and step["ok"], (mixer, pool, step)
    assert mixer["err"] > 0 and pool["err"] > 0 and pool["ms"] > 0 and pool["ms_ref"] > 0
    assert "4 blocks" in step["kernel"] and " complete chunks of 32" in pool["kernel"]
    assert seams["kernel"].startswith(pool["kernel"].split(" (")[0]) and seams["ok"], seams
    complete = lambda row: int(row["kernel"].split(" (")[1].split()[0])  # noqa: E731
    assert complete(seams) < complete(pool) < 32 and seams["ms"] > 0
    # lfm2_moe's three rows come last: a narrow head's gradients leave the backward head-major
    narrow, step, mixer = rows.pop(), rows.pop(), rows.pop()
    assert narrow["kernel"].startswith("lfm2_moe attn bwd over the band T512/H8:2/D16 bf16")
    assert narrow["ok"] and narrow["same_out_dk_dv"] and narrow["mean_dq"] <= narrow["mean_dq_lib"]
    assert mixer["kernel"].startswith("lfm2_moe shortconv mixer fwd+bwd B2/T128 bf16")
    assert step["kernel"].startswith("lfm2_moe step B2/T128 bf16 over a tail of 2 rows")
    assert mixer["ok"] and step["ok"] and mixer["err"] > 0 and step["err"] == 0  # the same numbers
    step, mixer = rows.pop(), rows.pop()  # latent attention's two rows come last
    assert mixer["kernel"].startswith("glm4_moe_lite mla mixer fwd+bwd B2/T128 bf16")
    assert step["kernel"].startswith("glm4_moe_lite mla step B2/T128 bf16 over a latent ring of 24")
    assert mixer["ok"] and step["ok"] and mixer["err"] > 0 and step["err"] > 0
    delta = rows.pop()  # the delta rule's pair against the jax.numpy body comes last
    assert delta["kernel"].startswith("gdn fwd+bwd B2/T40/H2:4x16:16/Q8 bf16") and delta["ok"]
    assert 0 < delta["err"] and delta["ms"] > 0 and delta["ms_ref"] > 0
    for row, kind in zip(rows[-2:], ("linear", "attention")):  # the two mixers come last
        assert row["kernel"].startswith(f"qwen3_next {kind} mixer fwd+bwd") and row["err"] > 0
    rows = rows[:-2]
    for row, band in zip(rows[7:9], (9, 10)):  # the seam-skipping rows follow the flash rows
        assert row["kernel"].startswith("flash seam-skipping") and row["err_vs_default"] == 0
        run = int(row["kernel"].split(": ")[1].split(" of")[0])
        assert f"of the band's {band} run" in row["kernel"] and 4 <= run < band
        assert row["ms"] > 0 and row["ms_ref"] > 0
    for row, band in zip(rows[9:12], (10, 9, 10)):  # the repo's own backward over the same bands
        assert row["kernel"].startswith("attn bwd over the band T512/H") and row["ok"], row
        assert f"of the band's {band} run" in row["kernel"] and row["same_out_dk_dv"]
        assert 0 < row["err"] <= row["tol"] and row["mean_dq"] <= row["mean_dq_lib"]
        assert row["ms"] > 0 and row["ms_ref"] > 0 and row["mosaic_calls"] == 0  # interpreted
    rows = rows[:7] + rows[12:]
    assert rows[10]["kernel"].startswith("row_add 512 rows (439 live) into 300x128")
    assert rows[10]["ms"] > 0 and rows[10]["ms_ref"] > 0 and rows[10]["err"] == 0
    assert "/window48 " in rows[6]["kernel"]
    for one, several in (rows[-5:-3], rows[-3:-1]):
        assert "1 trip)" in one["kernel"] and "2 trips)" in several["kernel"]
    assert "gated" in rows[-2]["kernel"] and "gated" not in rows[-4]["kernel"]
    assert "(chunk 512, 2 trips)" in rows[-1]["kernel"]  # every row held: the doubled fair share twice
    assert all(r["ok"] for r in rows), rows


def test_learner_phase_passes_tiny_interpreted():
    """The LearnerService-through-shm phase body, kernels interpreted, no
    device assertion; it still fails when the train step took another path
    than the one expected."""
    cfg = dict(algo="IMPALA", batch_size=8, seq_len=4, hidden_size=16,
               obs_shape=(4,), action_space=2)
    try:
        res = chip_smoke.phase_learner(
            cfg, updates=3, expect_path="lstm_pallas", require_tpu=False,
            pallas_mode="interpret",
        )
        assert res["platform"] == "cpu" and res["updates"] == 3
        assert res["paths"] == ["lstm_pallas"]
        assert res["compile_s"] > 0 and res["loss"] == res["loss"]
        with pytest.raises(chip_smoke.PhaseFailed, match="expected lstm_scan"):
            chip_smoke.phase_learner(
                cfg, updates=1, expect_path="lstm_scan", require_tpu=False,
                pallas_mode="interpret",
            )
    finally:
        from tpu_rl.models import cells

        cells.set_pallas_mode("auto")


@pytest.mark.slow
def test_cli_phase_fresh_then_resume_on_the_cpu(tmp_path):
    """The distributed CLI phase body end to end (worker -> manager ->
    storage -> learner, committed checkpoint, second invocation resumes)."""
    small = {**chip_smoke.REF, "hidden_size": 16, "batch_size": 16,
             "worker_num_envs": 8, "learner_device": "cpu"}
    work = str(tmp_path / "ppo")
    cpu = dict(require_tpu=False, expect_path="lstm_scan")
    res = chip_smoke.phase_cli("PPO", work, 8, small, **cpu)
    assert res["updates"] >= 8 and res["checkpoints"] >= 1
    res = chip_smoke.phase_cli("PPO", work, 8, small, resume=True, **cpu)
    assert res["updates"] >= 16 and res["resumed_from"] >= 8


@pytest.mark.slow
def test_multichip_steps_pass_tiny_on_virtual_devices():
    """The four-chip phase body on four virtual CPU devices: DP kernel
    islands and 2x2 ring attention at tiny widths, batch and train state
    checked to live on every device."""
    tiny = dict(algo="PPO", model="transformer", batch_size=8, seq_len=128,
                hidden_size=32, n_heads=2, n_layers=1, obs_shape=(4,),
                action_space=2)
    cases = {
        "dp-lstm-island": (
            dict(algo="PPO", hidden_size=16, seq_len=5, batch_size=16,
                 obs_shape=(4,), action_space=2), "lstm_pallas", None),
        "dp-flash-island": (
            {**tiny, "attention_impl": "flash"}, "attn_flash_pallas", None),
        "ring-2x2": (
            {**tiny, "attention_impl": "ring", "mesh_data": 2, "mesh_seq": 2},
            None, (2, 2)),
    }
    res = chip_smoke.multichip_steps(4, cases=cases, require_tpu=False)
    assert set(res["steps"]) == set(cases)
