"""The GLM-4.7-Flash configuration through the harness: the shipped files
load and hold the published widths, the reference's counts against hand
arithmetic at those widths, the two new readers on a made-up run, and a tiny
cell (8 experts all held, top-2) through ``open_loop`` as files and manifest
entries only, with the fp8 control and a planted fault."""

import json
import math
import os

import pytest

from chipbench import manifest, run, serve, trace

import chipbench_tiny as tiny

SMALL = os.path.join(os.path.dirname(trace.__file__), "testdata",
                     "small.xplane.pb")
CELL, CONFIG, MIX = ("glm47f-chat-open", "glm-4.7-flash-l7-bf16",
                     "chat1k-b32-open")
GLM = {
    "name": "glm-tiny", "builder": "zoo.Glm4MoeLite",
    "reference": "glm4_moe_lite", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 2, "first_k_dense_replace": 1,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1.8,
    "q_lora_rank": 24, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 1000000,
    "rms_norm_eps": 1e-5, "vocab_size": 211, "max_position_embeddings": 64,
    "num_nextn_predict_layers": 0, "kv_block_size": 8,
    "param_dtype": "float32", "kv_dtype": "float32",
    "control": "float8_e4m3fn", "limits": {"logit_gap_max": 1e-4},
}
TINY_MIX = dict(tiny.MIXES["tiny-open"], buckets="batch=8;seq=32",
                warm_prompt_lengths=[32], trace_after_s=0.2,
                trace_seconds=1.0)


# ------------------------------------------------------- the shipped files
def test_the_configuration_holds_the_published_widths():
    man = manifest.load_manifest()
    cell = manifest.Cell(man, CELL)
    cfg = cell.cfg
    assert (cell.spec["config"], cell.spec["traffic"], cell.chips) == \
        (CONFIG, MIX, 1)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "GLM-4.7-Flash")
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["published_num_hidden_layers"],
            cfg["num_nextn_predict_layers"],
            cfg["published_num_nextn_predict_layers"],
            cfg["max_position_embeddings"],
            cfg["published_max_position_embeddings"]) == \
        (7, 47, 0, 1, 1152, 202752)
    assert cfg["num_experts"] == cfg["n_routed_experts"] == 64
    for key in ("deployment", "assumed", "control", "limits", "kv_block_size",
                "param_dtype", "kv_dtype"):
        assert cfg[key]
    assert not any(k.endswith(("_dim", "_rank", "_size"))
                   for k in cfg["reduced"])


def test_the_mix_is_the_issues():
    mix = manifest.Cell(manifest.load_manifest(), CELL).mix
    want = {"kind": "open_loop", "trace_seed": 20261004, "clients": 256,
            "queue_limit": 256, "max_wait_ms": 5.0, "grace_s": 60,
            "check_requests": 8, "trace_after_s": 1.0, "trace_seconds": 10.0,
            "max_new_tokens": 128, "buckets": "batch=32;seq=1024",
            "warm_prompt_lengths": [800],
            "prompt_tokens": {"dist": "lognormal", "median": 512,
                              "sigma": 0.6, "min": 64, "max": 1024}}
    assert {k: mix[k] for k in want} == want
    assert "shuffle_block" not in mix
    assert mix["rate_per_s"] * 4 == int(mix["rate_per_s"] * 4)   # a quarter
    assert 64 + 128 <= 1024 + 128 <= 1152


def test_the_cell_reports_what_the_issue_lists():
    man = manifest.load_manifest()
    cell = manifest.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end()} == \
        {"serve_latency_p90_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer()} == {
        "gen_lateness_p95_ms", "sched_queue_wait_p50_s.open",
        "sched_batch_occupancy.open", "decode_step_device_ms.open",
        "decode_launch_gap_ms_p50.open", "device_idle_share.open",
        "prefill_device_ms.open", "moe_expert_load_max_over_mean",
        "serve_step_mfu.kimi", "decode_step_roofline.kimi",
        "moe_decode_touched_share", "prefill_roofline.glm47f",
        "compiles_in_window", "setup_cache_hit_share"}
    new = {m["name"]: m for m in man["per_layer"][-2:]}
    assert set(new) == {"moe_decode_touched_share", "prefill_roofline.glm47f"}
    assert all(m["workloads"] == [CELL] for m in new.values())


# ------------------------------------------------------ the model's counts
def _published():
    return manifest.Cell(manifest.load_manifest(), CELL).cfg


ATTN = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 \
    + 5120 * 2048                                     # 21.76 M
EXPERT = 3 * 2048 * 1536                              # 9.437 M
DENSE = 3 * 2048 * 10240
ROUTER = 2048 * 64
HEAD = 2048 * 154880


def test_the_cut_is_the_issues_arithmetic():
    ref = manifest.module_from("reference", "glm4_moe_lite")
    d = ref._dims(_published())
    count = lambda s: sum(math.prod(v) for v in s.values())
    layer = count(ref._layer_shapes(d, 1))
    assert layer == ATTN + ROUTER + 64 + 65 * EXPERT + 2 * 2048 + 768 + 512
    assert round(layer / 1e6, 1) == 635.3 and round(ATTN / 1e6, 2) == 21.76
    total = count(ref._layer_shapes(d, 0)) + 6 * layer + 2 * HEAD + 2048
    assert round(total / 1e6) == 4531


def test_request_flops_against_a_hand_count():
    ref = manifest.module_from("reference", "glm4_moe_lite")
    n, new = 500 + 128 - 1, 128
    token = 2 * (7 * ATTN + DENSE + 6 * (ROUTER + EXPERT + 4 * EXPERT))
    assert round(token / 2 / 1e6) == 499        # parameters a token meets
    want = n * token + 7 * 2 * 20 * (192 + 64 + 256) * (n * (n + 1) // 2) \
        + new * 2 * HEAD
    assert ref.request_flops(_published(), 500, 128) == pytest.approx(
        want, rel=1e-12)


def test_prefill_flops_against_a_hand_count():
    ref = manifest.module_from("reference", "glm4_moe_lite")
    token = 2 * (7 * ATTN + DENSE + 6 * (ROUTER + 5 * EXPERT))
    products = 32 * 1024 * token
    attention = 32 * 7 * 2 * 20 * 512 * (1024 * 1025 // 2)
    # causal pairs only: the full square the issue counted would be 4.8
    assert round(products / 1e12, 1) == 32.7 and \
        round(attention / 1e12, 1) == 2.4
    assert ref.prefill_flops(_published(), 32, 1024) == pytest.approx(
        products + attention + 32 * 2 * HEAD, rel=1e-12)


def test_decode_step_bytes_against_a_hand_count():
    ref = manifest.module_from("reference", "glm4_moe_lite")
    fixed = 7 * ATTN + DENSE + 6 * (ROUTER + EXPERT) + HEAD
    assert round(2 * fixed / 1e9, 2) == 1.18
    assert round(2 * EXPERT / 1e6, 2) == 18.87
    want = 2 * fixed + 330 * 2 * EXPERT + 32 * 600 * 7 * 1152
    assert round((330 * 2 * EXPERT) / 1e9, 1) == 6.2   # 6 layers x 55 experts
    assert ref.decode_step_bytes(_published(), 32, 32 * 600, 330) == want


# ---------------------------------------------- the readers on a made-up run
def _run(**more):
    out = {"cfg": dict(GLM), "mix": dict(TINY_MIX), "seconds": 2.0,
           "requests": [], "counters": {}, "trace": None,
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    out.update(more)
    return out


@pytest.mark.parametrize("name", ["moe_decode_touched_share",
                                  "prefill_roofline.glm47f"])
def test_a_new_reader_with_nothing_to_read_returns_none(name):
    read = manifest.module_from("metrics", name).read
    assert read(_run()) is None
    # a program older than the counters, a trace without the program
    assert read(_run(counters={"dl4j_serving_batches_total": 3.0},
                     trace={"module_s": {}, "module_n": {}})) is None
    # counters that did not move are nothing to read, never a share of 0
    assert read(_run(counters={
        "dl4j_serving_moe_decode_experts_touched_total": 0.0,
        "dl4j_serving_moe_decode_layer_steps_total": 0.0,
        "dl4j_serving_prefill_positions_total": 0.0,
        "dl4j_serving_prefill_launches_total": 0.0},
        trace={"module_s": {"jit__prefill_paged": 1.0},
               "module_n": {"jit__prefill_paged": 1}})) is None


def test_the_prefill_reader_needs_one_prompt_bucket_and_a_count():
    read = manifest.module_from("metrics", "prefill_roofline.glm47f").read
    full = dict(counters={"dl4j_serving_prefill_positions_total": 768.0,
                          "dl4j_serving_prefill_launches_total": 3.0},
                trace={"module_s": {"jit__prefill_paged": 0.003},
                       "module_n": {"jit__prefill_paged": 3}})
    assert read(_run(**full)) is not None
    assert read(_run(**full, mix=dict(TINY_MIX,
                                      buckets="batch=8;seq=16,32"))) is None
    assert read(_run(**full, cfg=dict(GLM, reference="kimi_linear",
                                      linear_attn_config={}))) is None


def test_the_new_readers_on_a_made_up_run():
    ref = manifest.module_from("reference", "glm4_moe_lite")
    made = _run(
        counters={"dl4j_serving_moe_decode_layer_steps_total": 40.0,
                  "dl4j_serving_moe_decode_experts_touched_total": 180.0,
                  "dl4j_serving_prefill_positions_total": 3 * 8 * 32.0,
                  "dl4j_serving_prefill_launches_total": 3.0},
        trace={"module_s": {"jit__prefill_paged": 0.003},
               "module_n": {"jit__prefill_paged": 3}})
    read = lambda n: manifest.module_from("metrics", n).read(made)
    # 180 touched over 40 layer-steps = 4.5 of the 8 held
    assert read("moe_decode_touched_share") == pytest.approx(56.25)
    least = ref.prefill_flops(GLM, 8, 32) / 197e12
    assert read("prefill_roofline.glm47f") == pytest.approx(
        least / 0.001 * 100)


# ------------------------------------------------- the tiny cell, end to end
@pytest.fixture
def tree(tmp_path, monkeypatch):
    """The tiny tree with the GLM cell added as a file and entries, listed
    wherever ``glm47f-chat-open`` is."""
    tiny.quiet_cache(monkeypatch)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / ".out"))
    man = tiny.tiny_tree(tmp_path, monkeypatch)
    path = "chipbench/configs/glm-tiny.json"
    with open(os.path.join(manifest.ROOT, path), "w") as f:
        json.dump(GLM, f)
    man["configs"].append({"name": "glm-tiny", "source": "test",
                           "file": path, "reduced": [], "why": "test"})
    with open(os.path.join(manifest.HERE, "traffic", "glm-open.json"),
              "w") as f:
        json.dump(TINY_MIX, f)
    man["workloads"].append({"name": "glm-tiny-open", "config": "glm-tiny",
                             "traffic": "glm-open", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("glm-tiny-open")
    return man


def measure(man, trace_on=False, seconds=1.5):
    return run.measure(manifest.Cell(man, "glm-tiny-open"), 2 ** 31 + 35,
                       seconds, trace_on, tiny.DEVICE)


def test_a_sound_open_loop_run_traced_reports_the_new_metrics(tree,
                                                             monkeypatch):
    # a CPU trace holds no TPU plane: the reduction reads the recorded one
    monkeypatch.setattr(trace, "reduce_logdir", lambda d: trace.reduce_trace(
        trace.read_planes(SMALL)))
    res = measure(tree, trace_on=True)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 5 and res["failed"] == 0
    assert res["info"]["serve_latency_p90_s"] > 0
    assert res["info"]["compiles_in_window"]["backend_compiles"] == 0
    m = res["metrics"]
    assert {"serve_step_mfu.kimi", "moe_expert_load_max_over_mean",
            "moe_decode_touched_share", "sched_batch_occupancy.open",
            "gen_lateness_p95_ms", "compiles_in_window"} <= set(m)
    # the recorded trace holds neither program: nothing to read
    assert "decode_step_roofline.kimi" not in m
    assert "prefill_roofline.glm47f" not in m
    assert 0 < m["serve_step_mfu.kimi"]["value"] < 100
    assert 12.5 <= m["moe_decode_touched_share"]["value"] <= 100
    assert 1.0 <= m["moe_expert_load_max_over_mean"]["value"] <= 8.0


def test_a_sound_run_untraced_reports_the_end_to_end_metrics(tree):
    res = measure(tree)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"serve_latency_p90_s", "setup_s"}
    assert res["failed"] == 0


def test_a_rotation_left_out_is_not_correct(tree, monkeypatch):
    """The planted fault: the served blocks carry the 8 rope dims
    unrotated."""
    from deeplearning4j_tpu.nn.decoder import HybridDecoderBlock

    monkeypatch.setattr(HybridDecoderBlock, "_rope",
                        lambda self, x, positions: x)
    cfg = dict(GLM, limits={"logit_gap_max": 1e-4})
    ref = manifest.module_from("reference", cfg["reference"])
    builder = manifest.module_from("builders", cfg["builder"])
    w = ref.make_weights(5, cfg)
    for p in w["layers"]:      # scores the softmax can see (test_glm4_moe_lite)
        p.update(Wuq=p["Wuq"] * 8, Wdkv=p["Wdkv"] * 8)
    net = builder.build(cfg)
    builder.load(net, w)
    from deeplearning4j_tpu.serving.generate import Generator

    gen = Generator(net, max_length=64, batch_buckets=(4,),
                    prefill_buckets=(32,), block_size=8)
    prompts = [[3 + i] * (9 + 4 * i) for i in range(4)]
    ok = [{"prompt": p, "tokens": t} for p, t in
          zip(prompts, gen.generate(prompts, max_new_tokens=6))]
    gaps = serve.served_gaps(ref, w, cfg, ok)
    assert float(gaps.max()) > 1e-4


def test_the_experts_of_a_layer_share_the_part_the_file_names():
    """``expert_common_share``: every element still N(0, 0.02), two experts
    of a layer correlated by the share, 0 = independent experts; the shipped
    file names a share between the two."""
    import numpy as np

    ref = manifest.module_from("reference", "glm4_moe_lite")
    corr = lambda a, b: float(np.corrcoef(np.ravel(a), np.ravel(b))[0, 1])
    for share in (0.0, 0.75):
        cfg = dict(GLM, expert_common_share=share, moe_intermediate_size=128)
        lyr = ref.make_weights(3, cfg)["layers"][1]
        for name in ("Egate", "Eup", "Edown"):
            e = np.asarray(lyr[name], np.float32)
            assert abs(e.std() - 0.02) < 1e-3
            assert abs(corr(e[0], e[1]) - share) < 0.03
            assert abs(corr(e[2], e[7]) - share) < 0.03
        assert abs(corr(lyr["Egate"][0], lyr["Eup"][0])) < 0.03
        assert abs(corr(lyr["Sgate"], lyr["Egate"][0])) < 0.03
    assert 0.5 < _published()["expert_common_share"] < 1.0


def test_picks_sent_to_the_neighbouring_expert_are_not_correct():
    """The planted fault under the shipped share of a common part: every
    pick computed by the next expert's matrices. The experts still differ,
    so the served tokens are not the reference's."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving.generate import Generator

    cfg = dict(GLM, expert_common_share=_published()["expert_common_share"])
    ref = manifest.module_from("reference", cfg["reference"])
    builder = manifest.module_from("builders", cfg["builder"])
    w = ref.make_weights(5, cfg)
    net = builder.build(cfg)
    builder.load(net, w)
    prompts = [[3 + i] * (9 + 4 * i) for i in range(4)]
    kw = dict(max_length=64, batch_buckets=(4,), prefill_buckets=(32,),
              block_size=8)
    sound = Generator(net, **kw).generate(prompts, max_new_tokens=6)
    ok = lambda out: [{"prompt": p, "tokens": t}
                      for p, t in zip(prompts, out)]
    assert float(serve.served_gaps(ref, w, cfg, ok(sound)).max()) <= 1e-4
    for lyr in net.params[2:-1]:
        for name in ("Egate", "Eup", "Edown"):
            lyr[name] = jnp.roll(lyr[name], 1, axis=0)
    wrong = Generator(net, **kw).generate(prompts, max_new_tokens=6)
    assert float(serve.served_gaps(ref, w, cfg, ok(wrong)).max()) > 1e-4


def test_the_fp8_control_is_not_correct(tree):
    """The control of ``chipbench.calibrate``: the reference computed with
    fp8 operands put in the program's place fails ``logit_gap_max``."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import checks

    cell = manifest.Cell(tree, "glm-tiny-open")
    cfg, mix = cell.cfg, cell.mix
    ref = manifest.module_from("reference", cfg["reference"])
    w = ref.make_weights(5, cfg)
    rng = np.random.default_rng(0)
    ok = [{"prompt": rng.integers(1, 211, size=20).tolist(),
           "tokens": rng.integers(1, 211, size=6).tolist()}
          for _ in range(4)]
    numbers = serve.judge(ref, w, cfg, mix, 5, ok,
                          control_dtype=jnp.dtype(cfg["control"]))
    assert checks.verdict(numbers) is False
    assert numbers[0]["name"] == "logit_gap_max"
    assert numbers[0]["value"] > cfg["limits"]["logit_gap_max"]
