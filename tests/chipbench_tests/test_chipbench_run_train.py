"""A whole run of the training kind at a tiny size on the CPU (the look for
a chip skipped), and the same with the timed path broken underneath:
``correct`` has to come out false for every fault a one-chip training cell
can have."""

import pytest

import jax.numpy as jnp

from chipbench import manifest, run

import chipbench_tiny as tiny


@pytest.fixture
def tree(tmp_path, monkeypatch):
    tiny.quiet_cache(monkeypatch)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / ".out"))
    return tiny.tiny_tree(tmp_path, monkeypatch)


def measure(man, name, planted=None, seconds=0.5):
    return run.measure(manifest.Cell(man, name), 2 ** 31 + 17, seconds,
                       False, tiny.DEVICE, planted=planted)


@pytest.mark.parametrize("seed_offset", [0, 1])
def test_a_sound_run_is_correct_and_reports_its_metrics(tree, seed_offset):
    res = run.measure(manifest.Cell(tree, "tiny-fit-staged"),
                      2 ** 31 + 17 + seed_offset, 0.5, False, tiny.DEVICE)
    assert res["correct"] is True, res["compared"]
    assert list(res)[-1] == "compared"
    assert list(res["compared"]) == [
        "loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
        "big_grad_norm_gap", "grad_angle.fc_w", "grad_angle.res3a_b_conv",
        "grad_angle.res4a_b_conv", "change_norm_gap"]
    assert set(res["metrics"]) == {"train_images_per_s_per_chip", "setup_s"}
    assert res["metrics"]["train_images_per_s_per_chip"]["value"] > 0
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["kind"] == "TPU v5 lite"
    assert all(c["value"] <= c["limit"] for c in res["compared"].values())


def test_the_step_clock_reads_losses_late_and_all_of_them():
    """``steps_ahead`` steps stay in flight; the close waits for every one,
    in the order they were sent."""
    import types

    from chipbench import train

    clock = train.StepClock()
    clock.ahead = 2
    for i in range(5):
        clock.iteration_done(types.SimpleNamespace(score_value=float(i)),
                             i, 0)
        assert len(clock.pending) == min(i + 1, 2)
    assert clock.losses == [0.0, 1.0, 2.0]
    clock.read()
    assert clock.losses == [0.0, 1.0, 2.0, 3.0, 4.0] and not clock.pending
    assert len(clock.ends) == 5 and clock.ends == sorted(clock.ends)


def unchanged_state(prog):
    """A step that returns its state unchanged."""
    def step(params, states, opts, it, key, *rest):
        return params, states, opts, jnp.float32(2.3), it + 1, key
    prog.net._train_step = step


def half_batch(prog):
    """Half of the batch left out, the mean taken over the rest."""
    inner = prog.net._fit_batch
    prog.net._fit_batch = lambda x, y: inner(x[:len(x) // 2],
                                             y[:len(y) // 2])


def one_conv_gradient_wrong(prog):
    """A backward bug in a single leaf: one convolution's gradient reaches
    the optimizer reversed along its output channels. Every norm and every
    loss of the first step is as it should be."""
    inner = prog.builder.adam_m

    def adam_m(net):
        m = dict(inner(net))
        m["res4a_b_conv"] = m["res4a_b_conv"][..., ::-1]
        return m
    prog.builder.adam_m = adam_m


def one_conv_gradient_half_size(prog):
    """A backward bug in a single leaf: one convolution's gradient reaches
    the optimizer at half its size (a term of it dropped). The median leaf
    does not see it; the worst big leaf does."""
    inner = prog.builder.adam_m

    def adam_m(net):
        m = dict(inner(net))
        m["res3b_b_conv"] = 0.5 * m["res3b_b_conv"]
        return m
    prog.builder.adam_m = adam_m


@pytest.mark.parametrize("name,fault", [
    ("tiny-fit-staged", unchanged_state),
    ("tiny-fit-staged", half_batch),
    ("tiny-fit-staged", one_conv_gradient_wrong),
    ("tiny-fit-staged", one_conv_gradient_half_size),
], ids=lambda v: getattr(v, "__name__", v))
def test_a_broken_timed_path_is_not_correct(tree, name, fault):
    res = measure(tree, name, planted=fault)
    assert res["correct"] is False
    over = [k for k, c in res["compared"].items() if c["value"] > c["limit"]]
    assert over, res["compared"]


def test_the_peak_counts_what_the_compiled_step_reserves(tree):
    """``memory_stats`` counts live arrays only; the step's temporaries come
    from its ``memory_analysis()`` through the builder."""
    from chipbench import train

    cell = manifest.Cell(tree, "tiny-fit-staged")
    prog = train.Program(cell.cfg, cell.mix, 5, 1)
    prog.first_steps()
    mem = prog.step_memory()
    assert set(mem) == {"argument_bytes", "output_bytes", "alias_bytes",
                        "temp_bytes"}
    assert mem["temp_bytes"] > 0 and mem["argument_bytes"] > 0
    ctx = run.Context(cell, 5, 0.1, False)
    ctx._compiles_at_close = {}
    assert ctx.memory_peak_bytes(mem) >= (
        mem["temp_bytes"] + mem["output_bytes"] - mem["alias_bytes"])


def test_calibrate_rows_carry_the_harness_verdict(tree, capsys, monkeypatch):
    """The readings' rows say what ``checks.verdict`` says of them under the
    cell's own limits: the control and the fault come out not correct."""
    import json

    from chipbench import calibrate

    monkeypatch.setattr(run, "find_devices", lambda chips: tiny.DEVICE)
    calibrate.main(["--workload", "tiny-fit-staged", "--seeds", "7",
                    "--control-seeds", "7"])
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    by = {r["who"]: r for r in rows}
    assert by["program"]["correct"] is True and by["program"]["over"] == []
    assert by["control"]["correct"] is False and by["control"]["over"]
    assert by["fault_half_batch"]["correct"] is False
    assert len(by["program_angles"]) == 2 + 161      # who, seed, every leaf
    assert len(by["fault_half_batch_grad_gaps"]) == 2 + 161
    assert "overrides" not in by["program"]
