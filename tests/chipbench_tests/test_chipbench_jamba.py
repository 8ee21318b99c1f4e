"""The AI21-Jamba2-3B configuration through the harness: the shipped files
load and hold every published key, the reference's counts against hand
arithmetic at those widths, the three new readers on a made-up run, and a
tiny cell (one whole period: Mamba, attention over one K/V head, Mamba,
Mamba) through ``open_loop`` as files and manifest entries only, with the
fp8 control and the planted faults."""

import json
import math
import os

import pytest

from chipbench import manifest, run, serve, trace

import chipbench_tiny as tiny

SMALL = os.path.join(os.path.dirname(trace.__file__), "testdata",
                     "small.xplane.pb")
CELL, CONFIG, MIX = ("jamba2-chat-open", "jamba2-3b-bf16",
                     "chat256-b64-open")
JAMBA = {
    "name": "jamba-tiny", "builder": "zoo.Jamba", "reference": "jamba",
    "attn_layer_offset": 1, "attn_layer_period": 4, "hidden_size": 64,
    "intermediate_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 8, "mamba_dt_rank": 8, "mamba_expand": 2,
    "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
    "num_hidden_layers": 4, "rms_norm_eps": 1e-6, "vocab_size": 211,
    "max_position_embeddings": 64, "tie_word_embeddings": True,
    "kv_block_size": 8, "param_dtype": "float32", "kv_dtype": "float32",
    "state_dtype": "float32", "control": "float8_e4m3fn",
    "limits": {"logit_gap_max": 1e-4},
}
TINY_MIX = dict(tiny.MIXES["tiny-open"], buckets="batch=8;seq=32",
                warm_prompt_lengths=[32], trace_after_s=0.2,
                trace_seconds=1.0)
NEW = ("decode_step_roofline.jamba2", "ssm_step_roofline.jamba2",
       "ssm_scan_roofline.jamba2")


# ------------------------------------------------------- the shipped files
def _published():
    return manifest.Cell(manifest.load_manifest(), CELL).cfg


def test_the_configuration_holds_every_published_key():
    man = manifest.load_manifest()
    cell = manifest.Cell(man, CELL)
    cfg = cell.cfg
    assert (cell.spec["config"], cell.spec["traffic"], cell.chips) == \
        (CONFIG, MIX, 1)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "AI21-Jamba2-3B")
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"] == ["max_position_embeddings"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == ["max_position_embeddings"]
    assert (cfg["max_position_embeddings"],
            cfg["published_max_position_embeddings"]) == (384, 262144)
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    for key in ("deployment", "assumed", "control", "limits", "kv_block_size",
                "param_dtype", "kv_dtype", "state_dtype"):
        assert cfg[key]
    assert not any(k.endswith(("_dim", "_rank", "_size"))
                   for k in cfg["reduced"])


def test_the_mix_is_the_issues():
    mix = manifest.Cell(manifest.load_manifest(), CELL).mix
    want = {"kind": "open_loop", "trace_seed": 20261005, "clients": 256,
            "queue_limit": 256, "max_wait_ms": 5.0, "grace_s": 60,
            "check_requests": 8, "trace_after_s": 1.0, "trace_seconds": 10.0,
            "max_new_tokens": 128, "buckets": "batch=64;seq=256",
            "warm_prompt_lengths": [200],
            "prompt_tokens": {"dist": "lognormal", "median": 96,
                              "sigma": 0.6, "min": 32, "max": 256}}
    assert {k: mix[k] for k in want} == want
    assert "shuffle_block" not in mix
    assert mix["rate_per_s"] == int(mix["rate_per_s"])  # a whole request a s
    assert 256 + 128 <= _published()["max_position_embeddings"]


def test_the_cell_reports_what_the_issue_lists():
    man = manifest.load_manifest()
    cell = manifest.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end()} == \
        {"serve_latency_p90_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer()} == {
        "gen_lateness_p95_ms", "sched_queue_wait_p50_s.open",
        "sched_batch_occupancy.open", "decode_step_device_ms.open",
        "decode_launch_gap_ms_p50.open", "device_idle_share.open",
        "prefill_device_ms.open", "serve_step_mfu.kimi",
        "prefill_roofline.glm47f", "compiles_in_window",
        "setup_cache_hit_share", *NEW}
    # by name: the next PR appends behind them
    new = {m["name"]: m for m in man["per_layer"] if m["name"] in NEW}
    assert set(new) == set(NEW)
    for m in new.values():
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert (m["layer"], m["moves"]) == ("kernels", "serve_latency_p90_s")


# ------------------------------------------------------ the model's counts
MAMBA = 2560 * 10240 + 5120 * 2560 + 5120 * 192 + 160 * 5120   # matrices
MAMBA_SMALL = 5120 + 5120 * 16 + 4 * 5120 + 5120 + 5120 + 160 + 16 + 16
FFN = 3 * 2560 * 8192
ATTN = 2 * 2560 * 2560 + 2 * 2560 * 128
EMB = 65536 * 2560


def test_nothing_is_cut_the_issues_arithmetic():
    ref = manifest.module_from("reference", "jamba")
    d = ref._dims(_published())
    count = lambda s: sum(math.prod(v) for v in s.values())
    mamba = count(ref._layer_shapes(d, 0))
    attn = count(ref._layer_shapes(d, 7))
    assert mamba == MAMBA + MAMBA_SMALL + FFN + 2 * 2560
    assert attn == ATTN + FFN + 2 * 2560
    assert round((MAMBA + MAMBA_SMALL) / 1e6, 2) == 41.24
    assert round(mamba / 1e6, 2) == 104.16 and round(attn / 1e6, 2) == 76.68
    layers = [count(ref._layer_shapes(d, i)) for i in range(28)]
    assert [i for i, n in enumerate(layers) if n == attn] == [7, 21]
    total = sum(layers) + EMB + 2560
    assert round(total / 1e6) == 3029 and round(2 * total / 1e9, 2) == 6.06


def test_request_flops_against_a_hand_count():
    ref = manifest.module_from("reference", "jamba")
    n, new = 100 + 128 - 1, 128
    products = 2 * (26 * (MAMBA + FFN) + 2 * (ATTN + FFN))
    assert round(products / 2 / 1e6) == 2858     # in matrices a token meets
    scan = 26 * 5120 * (7 * 16 + 2 * 4)
    want = n * (products + scan) + 2 * 4 * 20 * 128 * (n * (n + 1) // 2) \
        + new * 2 * EMB
    assert ref.request_flops(_published(), 100, 128) == pytest.approx(
        want, rel=1e-12)


def test_prefill_flops_against_a_hand_count():
    ref = manifest.module_from("reference", "jamba")
    products = 64 * 256 * 2 * (26 * (MAMBA + FFN) + 2 * (ATTN + FFN))
    attention = 64 * 2 * 4 * 20 * 128 * (256 * 257 // 2)
    assert round(products / 1e12, 1) == 93.7
    assert ref.prefill_flops(_published(), 64, 256) == pytest.approx(
        products + attention + 64 * 2 * EMB, rel=1e-12)


def test_the_bytes_against_a_hand_count():
    ref = manifest.module_from("reference", "jamba")
    cfg = _published()
    state = 4 * (5120 * 16 + 3 * 5120)
    assert state == 327680 + 61440 and round(26 * state / 1e6, 1) == 10.1
    fixed = 26 * (MAMBA + FFN) + 2 * (ATTN + FFN) + EMB
    want = 2 * fixed + 64 * 26 * 2 * state + 64 * 200 * 2 * 512
    assert ref.decode_step_bytes(cfg, 64, 64 * 200) == want
    assert round(want / 1e9, 2) == 7.36             # 9.0 ms at 819 GB/s
    assert ref.ssm_step_bytes(cfg, 40) == 4 * 40 * (
        2 * 5120 * 16 + 4 * 5120 + 2 * 16)
    assert ref.ssm_scan_bytes(cfg, 64, 8000) == 4 * (
        8000 * (4 * 5120 + 2 * 16) + 64 * 2 * 5120 * 16)


# ---------------------------------------------- the readers on a made-up run
def _run(**more):
    out = {"cfg": dict(JAMBA), "mix": dict(TINY_MIX), "seconds": 2.0,
           "requests": [], "counters": {}, "trace": None,
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    out.update(more)
    return out


def _trace(**op_s):
    return {"module_s": {"jit__prefill_paged": 0.003,
                         "jit__decode_paged": 0.02},
            "module_n": {"jit__prefill_paged": 3, "jit__decode_paged": 20},
            "op_s": op_s}


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_with_nothing_to_read_returns_none(name):
    read = manifest.module_from("metrics", name).read
    assert read(_run()) is None
    # a trace without the programs
    assert read(_run(counters={"dl4j_serving_batches_total": 3.0},
                     trace={"module_s": {}, "module_n": {}, "op_s": {}})) \
        is None
    # counters that did not move, a trace without the kernels' ops
    assert read(_run(counters={
        "dl4j_serving_batches_total": 0.0,
        "dl4j_serving_ssm_decode_states_live_total": 0.0,
        "dl4j_serving_ssm_decode_states_declared_total": 0.0,
        "dl4j_serving_ssm_prefill_positions_live_total": 0.0,
        "dl4j_serving_prefill_launches_total": 0.0}, trace=_trace())) is None


DONE = [{"status": 200, "prompt": [1] * 10, "tokens": [2] * 6, "done": 1.0}
        for _ in range(12)]
COUNTERS = {"dl4j_serving_batches_total": 3.0,
            "dl4j_serving_completed_total": 12.0,
            "dl4j_serving_prefill_launches_total": 3.0,
            "dl4j_serving_ssm_prefill_positions_live_total": 3.0 * 132,
            "dl4j_serving_ssm_decode_states_live_total": 3.0 * 4 * 20,
            "dl4j_serving_ssm_decode_states_declared_total": 3.0 * 8 * 20}


def test_the_kernel_readers_need_their_op_in_the_trace():
    for name in NEW[1:]:
        read = manifest.module_from("metrics", name).read
        assert read(_run(requests=DONE, counters=COUNTERS,
                         trace=_trace(**{"fusion.3 f32[8,64]": 1.0}))) is None


def test_the_new_readers_on_a_made_up_run():
    ref = manifest.module_from("reference", "jamba")
    made = _run(requests=DONE, counters=COUNTERS, trace=_trace(**{
        "ssm_step.3 f32[8,128]": 0.0004, "ssm_step.4 f32[8,128]": 0.0002,
        "ssm_scan.1 f32[8,128,128]": 0.0009, "fusion.1 f32[8,64]": 0.5}))
    read = lambda n: manifest.module_from("metrics", n).read(made)
    # 4 streams a batch of 10 + 3 tokens each; a step is 1 ms
    least = ref.decode_step_bytes(JAMBA, 4, 4 * 13) / 819e9
    assert read(NEW[0]) == pytest.approx(least / 0.001 * 100)
    # 3 Mamba layers x 20 launches share 0.6 ms; 4 of the 8 rows are live
    least = ref.ssm_step_bytes(JAMBA, 4) / 819e9
    assert read(NEW[1]) == pytest.approx(least / (0.0006 / 60) * 100)
    # 3 layers x 3 launches share 0.9 ms; 44 live positions in 4 rows each
    least = ref.ssm_scan_bytes(JAMBA, 4, 44) / 819e9
    assert read(NEW[2]) == pytest.approx(least / (0.0009 / 9) * 100)
    for name in NEW:
        assert 0 < read(name) <= 100


# ------------------------------------------------- the tiny cell, end to end
@pytest.fixture
def tree(tmp_path, monkeypatch):
    """The tiny tree with the Jamba cell added as a file and entries,
    listed wherever ``jamba2-chat-open`` is."""
    tiny.quiet_cache(monkeypatch)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / ".out"))
    man = tiny.tiny_tree(tmp_path, monkeypatch)
    path = "chipbench/configs/jamba-tiny.json"
    with open(os.path.join(manifest.ROOT, path), "w") as f:
        json.dump(JAMBA, f)
    man["configs"].append({"name": "jamba-tiny", "source": "test",
                           "file": path, "reduced": [], "why": "test"})
    with open(os.path.join(manifest.HERE, "traffic", "jamba-open.json"),
              "w") as f:
        json.dump(TINY_MIX, f)
    man["workloads"].append({"name": "jamba-tiny-open",
                             "config": "jamba-tiny", "traffic": "jamba-open",
                             "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("jamba-tiny-open")
    return man


def measure(man, trace_on=False, seconds=1.5):
    return run.measure(manifest.Cell(man, "jamba-tiny-open"), 2 ** 31 + 37,
                       seconds, trace_on, tiny.DEVICE)


def test_a_sound_open_loop_run_traced_reports_what_it_can_read(tree,
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
    assert {"serve_step_mfu.kimi", "sched_batch_occupancy.open",
            "gen_lateness_p95_ms", "compiles_in_window"} <= set(m)
    # the recorded trace holds neither program nor kernel: nothing to read
    assert not set(NEW) & set(m) and "prefill_roofline.glm47f" not in m
    assert 0 < m["serve_step_mfu.kimi"]["value"] < 100


def test_a_sound_run_untraced_reports_the_end_to_end_metrics(tree):
    res = measure(tree)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"serve_latency_p90_s", "setup_s"}
    assert res["failed"] == 0


# ----------------------------------------------------- the planted faults
NEW_TOKENS = 32


@pytest.fixture(scope="module")
def sound():
    """(reference, weights, builder, prompts) of the tiny model, its
    weights made so that every term of the mixer moves a logit of the 4 x 32
    served tokens: what the layers add large beside the embedding (as at the
    published widths, where it is 45 times the embedding's size), a bias and
    norm scales that are not nearly 0 and 1, steps near 0.7 into a state
    that decays slowly (so its precision shows) beside a smaller skip."""
    import numpy as np

    ref = manifest.module_from("reference", "jamba")
    builder = manifest.module_from("builders", "zoo.Jamba")
    w = ref.make_weights(5, JAMBA)
    for p in w["layers"]:
        if "Win" in p:
            p.update(conv_bias=p["conv_bias"] * 25,
                     dt_norm=p["dt_norm"] * 3, B_norm=p["B_norm"] * 2,
                     C_norm=p["C_norm"] * 2, Wout=p["Wout"] * 8,
                     Win=p["Win"] * 4, D=p["D"] * 0.3,
                     A_log=p["A_log"] * 0 + np.log(0.05),
                     dt_bias=p["dt_bias"] * 0)
    prompts = [[3 + i] * (9 + 4 * i) for i in range(4)]
    return ref, w, builder, prompts


def _served(sound):
    """What the program as it now stands serves."""
    from deeplearning4j_tpu.serving.generate import Generator

    ref, w, builder, prompts = sound
    net = builder.build(JAMBA)
    builder.load(net, w)
    gen = Generator(net, max_length=64, batch_buckets=(4,),
                    prefill_buckets=(32,), block_size=8)
    return [{"prompt": p, "tokens": t} for p, t in
            zip(prompts, gen.generate(prompts, max_new_tokens=NEW_TOKENS))]


def _gap(sound):
    """The worst served logit's gap of the program as it now stands."""
    ref, w, _, _ = sound
    return float(serve.served_gaps(ref, w, JAMBA, _served(sound)).max())


def test_the_program_as_it_stands_is_correct_and_the_fp8_control_is_not(
        sound):
    """The control of ``chipbench.calibrate``: the reference computed with
    fp8 operands put in the program's place fails ``logit_gap_max``."""
    import jax.numpy as jnp

    from chipbench import checks

    ref, w, _, _ = sound
    ok, mix = _served(sound), dict(TINY_MIX, max_new_tokens=NEW_TOKENS)
    numbers = serve.judge(ref, w, JAMBA, mix, 5, ok)
    assert checks.verdict(numbers) is True, numbers
    numbers = serve.judge(ref, w, JAMBA, mix, 5, ok,
                          control_dtype=jnp.dtype(JAMBA["control"]))
    assert checks.verdict(numbers) is False
    assert numbers[0]["name"] == "logit_gap_max"
    assert numbers[0]["value"] > JAMBA["limits"]["logit_gap_max"]


FAULTS = ["the dt, B, C norms left out", "D x dropped",
          "the convolution's bias dropped", "the state carried in bfloat16",
          "a padded position advancing the state"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(sound, monkeypatch, fault):
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.decoder import HybridDecoderBlock as Block
    from deeplearning4j_tpu.ops import ssm

    if fault == FAULTS[0]:
        from deeplearning4j_tpu.nn import decoder

        real = Block._ssm_inputs

        def no_norms(self, params, h, tail):
            with pytest.MonkeyPatch.context() as inner:
                inner.setattr(decoder, "rms_norm",
                              lambda a, w, eps: a.astype(jnp.float32))
                return real(self, params, h, tail)

        monkeypatch.setattr(Block, "_ssm_inputs", no_norms)
    elif fault == FAULTS[1]:
        real = Block._ssm_rates
        monkeypatch.setattr(Block, "_ssm_rates", lambda self, p: (
            real(self, p)[0], real(self, p)[1] * 0))
    elif fault == FAULTS[2]:
        real = Block._ssm_inputs
        monkeypatch.setattr(Block, "_ssm_inputs", lambda self, p, h, t: real(
            self, {**p, "conv_bias": p["conv_bias"] * 0}, h, t))
    elif fault == FAULTS[3]:
        real_scan, real_step = ssm.selective_scan, ssm.selective_step_paged
        low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

        def scan(*a, **kw):
            y, s = real_scan(*a, **kw)
            return y, low(s)

        def step(x, dt, A, B, C, D, pool, *a, **kw):
            y, pool = real_step(x, dt, A, B, C, D, low(pool), *a, **kw)
            return y, low(pool)

        monkeypatch.setattr(ssm, "selective_scan", scan)
        monkeypatch.setattr(ssm, "selective_step_paged", step)
    else:
        real_scan = ssm.selective_scan
        monkeypatch.setattr(ssm, "selective_scan", lambda x, dt, A, B, C, D,
                            s, lengths, z: real_scan(
                                x, dt, A, B, C, D, s, None, z))
    assert _gap(sound) > 1e-4
