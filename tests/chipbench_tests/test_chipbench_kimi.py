"""The Kimi Linear configuration through the harness at a tiny size on the
CPU: a share (4 of 8 experts held) through ``closed_loop`` and ``open_loop``
as files and manifest entries only, the fp8 control and a planted fault, the
reference's counts against a hand count, and each new reader on a made-up
run."""

import json
import os

import pytest

from chipbench import manifest, run, serve, trace

import chipbench_tiny as tiny

SMALL = os.path.join(os.path.dirname(trace.__file__), "testdata",
                     "small.xplane.pb")
KIMI = {
    "name": "kimi-tiny", "builder": "zoo.KimiLinear",
    "reference": "kimi_linear", "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 2, "first_k_dense_replace": 1,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_experts": 4,
    "published_num_experts": 8, "expert_offset": 2,
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "kv_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rms_norm_eps": 1e-5, "vocab_size": 211, "max_position_embeddings": 64,
    "gate_low_rank": 8, "kv_block_size": 8, "param_dtype": "float32",
    "kv_dtype": "float32", "state_dtype": "float32",
    "linear_attn_config": {"full_attn_layers": [4], "kda_layers": [1, 2, 3],
                           "head_dim": 16, "num_heads": 2,
                           "short_conv_kernel_size": 4},
    "control": "float8_e4m3fn", "limits": {"logit_gap_max": 1e-4},
}
# one bucket each: one prefill and one decode program a run
MIXES = {
    "kimi-closed": dict(tiny.MIXES["tiny-closed"], buckets="batch=4;seq=48",
                        warm_prompt_lengths=[40]),
    "kimi-open": dict(tiny.MIXES["tiny-open"], buckets="batch=8;seq=32",
                      warm_prompt_lengths=[32], trace_after_s=0.2,
                      trace_seconds=1.0),
}
CELLS = {"kimi-tiny-closed": "kimi-closed", "kimi-tiny-open": "kimi-open"}


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """The tiny tree with the Kimi cells added as files and entries, each
    listed wherever ``kimiL-chat-open`` is."""
    tiny.quiet_cache(monkeypatch)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / ".out"))
    man = tiny.tiny_tree(tmp_path, monkeypatch)
    path = "chipbench/configs/kimi-tiny.json"
    with open(os.path.join(manifest.ROOT, path), "w") as f:
        json.dump(KIMI, f)
    man["configs"].append({"name": "kimi-tiny", "source": "test",
                           "file": path, "reduced": [], "why": "test"})
    for name, mix in MIXES.items():
        with open(os.path.join(manifest.HERE, "traffic", name + ".json"),
                  "w") as f:
            json.dump(mix, f)
    man["workloads"] += [{"name": n, "config": "kimi-tiny", "traffic": t,
                          "chips": 1, "why": "test"}
                         for n, t in CELLS.items()]
    for m in man["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("kimi-tiny-closed")
    for m in man["end_to_end"] + man["per_layer"]:
        if "kimiL-chat-open" in m.get("workloads", ()):
            m["workloads"].append("kimi-tiny-open")
    return man


def measure(man, name, trace_on=False, seconds=1.5):
    return run.measure(manifest.Cell(man, name), 2 ** 31 + 29, seconds,
                       trace_on, tiny.DEVICE)


def test_a_sound_closed_loop_run_is_correct(tree):
    res = measure(tree, "kimi-tiny-closed")
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["attempted"] > 5 and res["failed"] == 0
    assert res["info"]["compiles_in_window"]["backend_compiles"] == 0


def test_a_state_not_reset_between_streams_is_not_correct(tree, monkeypatch):
    """The planted fault: a KDA prefill that starts from what the slot's
    last stream left there, not from an empty state."""
    from deeplearning4j_tpu.nn.decoder import HybridDecoderBlock
    from deeplearning4j_tpu.ops import kda

    sound = HybridDecoderBlock.prefill_paged

    def faulty(self, params, x, pool, where, mask=None):
        if self.mixer != "kda":
            return sound(self, params, x, pool, where, mask=mask)
        real, left = kda.kda_chunked, pool["state"][where]
        kda.kda_chunked = lambda *a: real(*a[:5], a[5] + left)
        try:
            return sound(self, params, x, pool, where, mask=mask)
        finally:
            kda.kda_chunked = real

    monkeypatch.setattr(HybridDecoderBlock, "prefill_paged", faulty)
    res = measure(tree, "kimi-tiny-open")
    assert res["correct"] is False
    gap = res["compared"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_the_fp8_control_is_not_correct(tree):
    """The control of ``chipbench.calibrate``: the reference computed with
    fp8 operands put in the program's place fails ``logit_gap_max``."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import checks

    cell = manifest.Cell(tree, "kimi-tiny-open")
    cfg, mix = cell.cfg, cell.mix
    ref = manifest.module_from("reference", cfg["reference"])
    w = ref.make_weights(5, cfg)
    rng = np.random.default_rng(0)
    ok = [{"prompt": rng.integers(1, 211, size=20).tolist(),
           "tokens": rng.integers(1, 211, size=6).tolist()}
          for _ in range(4)]
    sound = serve.served_gaps(ref, w, cfg, ok)
    assert sound.shape == (24,) and float(sound.max()) > 1e-4, \
        "random tokens are not the reference's: the comparison sees them"
    numbers = serve.judge(ref, w, cfg, mix, 5, ok,
                          control_dtype=jnp.dtype(cfg["control"]))
    assert checks.verdict(numbers) is False
    assert numbers[0]["name"] == "logit_gap_max"
    assert numbers[0]["value"] > cfg["limits"]["logit_gap_max"]


def test_a_sound_open_loop_run_traced_reports_the_new_metrics(tree,
                                                             monkeypatch):
    # a CPU trace holds no TPU plane: the reduction reads the recorded one
    monkeypatch.setattr(trace, "reduce_logdir", lambda d: trace.reduce_trace(
        trace.read_planes(SMALL)))
    res = measure(tree, "kimi-tiny-open", trace_on=True)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 5 and res["failed"] == 0
    assert res["info"]["serve_latency_p90_s"] > 0
    assert res["info"]["compiles_in_window"]["backend_compiles"] == 0
    m = res["metrics"]
    assert {"serve_step_mfu.kimi", "moe_expert_load_max_over_mean",
            "sched_batch_occupancy.open", "gen_lateness_p95_ms",
            "compiles_in_window"} <= set(m)
    # the recorded trace holds no jit__decode_paged: nothing to read
    assert "decode_step_roofline.kimi" not in m
    assert "prefill_device_ms.open" not in m
    assert 0 < m["serve_step_mfu.kimi"]["value"] < 100
    assert 1.0 <= m["moe_expert_load_max_over_mean"]["value"] <= 4.0


# ------------------------------------------------------ the model's counts
def test_request_flops_against_a_hand_count():
    ref = manifest.module_from("reference", "kimi_linear")
    h, n, new = 64, 10 + 5 - 1, 5
    kda_mixer = 3 * h * 32 + h * 8 + 8 * 32 + h * 2 + h * 8 + 8 * 32 + 32 * h
    mla_mixer = h * 32 + 24 * 2 * 32 + h * 2 * 24 + 32 * h
    dense = 3 * h * 128
    # router + shared expert + 2 picks x 4 of 8 experts held = 1 expert
    routed = h * 8 + 3 * h * 32 + 1.0 * 3 * h * 32
    kda_state = 7 * 32 * 16 + 2 * 3 * 32 * 4
    want = 2 * n * (3 * kda_mixer + mla_mixer + dense + 3 * routed) \
        + 3 * n * kda_state \
        + 2 * 2 * (16 + 8 + 16) * (n * (n + 1) // 2) \
        + new * 2 * h * 211
    assert ref.request_flops(KIMI, 10, 5) == pytest.approx(want, rel=1e-12)


def test_decode_step_bytes_against_a_hand_count():
    ref = manifest.module_from("reference", "kimi_linear")
    h = 64
    kda_mixer = 3 * h * 32 + h * 8 + 8 * 32 + h * 2 + h * 8 + 8 * 32 + 32 * h
    mla_mixer = h * 32 + 24 * 2 * 32 + h * 2 * 24 + 32 * h
    fixed = 3 * kda_mixer + mla_mixer + 3 * h * 128 \
        + 3 * (h * 8 + 3 * h * 32) + h * 211
    state = 4 * (32 * 16 + 3 * 3 * 32)
    want = 4 * fixed + 5 * 3 * h * 32 * 4 + 3 * 3 * 2 * state \
        + 100 * 1 * 4 * 32
    assert ref.decode_step_bytes(KIMI, 3, 100, 5) == want


# ---------------------------------------------- the readers on a made-up run
def _run(**more):
    out = {"cfg": dict(KIMI), "seconds": 2.0, "requests": [], "counters": {},
           "trace": None,
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    out.update(more)
    return out


@pytest.mark.parametrize("name", [
    "serve_step_mfu.kimi", "decode_step_roofline.kimi",
    "prefill_device_ms.open", "moe_expert_load_max_over_mean"])
def test_a_reader_with_nothing_to_read_returns_none(name):
    read = manifest.module_from("metrics", name).read
    assert read(_run()) is None
    # a program without the counters, a trace without the programs
    assert read(_run(counters={"dl4j_serving_batches_total": 3.0},
                     trace={"module_s": {}, "module_n": {}})) is None


def test_the_readers_on_a_made_up_run():
    ref = manifest.module_from("reference", "kimi_linear")
    reqs = [{"status": 200, "done": 1.0, "prompt": [1] * 20,
             "tokens": [2] * 6},
            {"status": 200, "done": 3.0, "prompt": [1] * 20,
             "tokens": [2] * 6}]
    made = _run(
        requests=reqs,
        counters={"dl4j_serving_batches_total": 2.0,
                  "dl4j_serving_completed_total": 2.0,
                  "dl4j_serving_moe_decode_layer_steps_total": 30.0,
                  "dl4j_serving_moe_decode_experts_touched_total": 45.0,
                  "dl4j_serving_moe_picks_local_total": 400.0,
                  "dl4j_serving_moe_expert_load_max_total": 150.0},
        trace={"module_s": {"jit__decode_paged": 0.01,
                            "jit__prefill_paged": 0.004},
               "module_n": {"jit__decode_paged": 10,
                            "jit__prefill_paged": 2}})
    read = lambda n: manifest.module_from("metrics", n).read(made)
    # only the request done inside the window counts
    assert read("serve_step_mfu.kimi") == pytest.approx(
        ref.request_flops(KIMI, 20, 6) / (2.0 * 197e12) * 100)
    # 30 layer-steps over 3 routed layers = 10 steps; 45 / 10 touched a step
    least = ref.decode_step_bytes(KIMI, 1.0, 23.0, 4.5) / 819e9
    assert read("decode_step_roofline.kimi") == pytest.approx(
        least / 0.001 * 100)
    assert read("prefill_device_ms.open") == pytest.approx(2.0)
    assert read("moe_expert_load_max_over_mean") == pytest.approx(1.5)
