"""The Olmo-Hybrid-7B configuration through the harness: the shipped files
hold every published key but those cut, the reference's counts against hand
arithmetic at the published widths, the three new readers on a made-up
run, and a tiny cell (one whole period: three Gated DeltaNet layers and a
QK-normed full layer, post-norm) through ``open_loop`` as files and
manifest entries only, with the fp8 control and planted faults."""

import json
import math
import os

import pytest

from chipbench import manifest, run, serve, trace

import chipbench_tiny as tiny

SMALL = os.path.join(os.path.dirname(trace.__file__), "testdata",
                     "small.xplane.pb")
CELL, CONFIG, MIX = ("olmoh-chat-open", "olmo-hybrid-7b-l16-bf16",
                     "chat256-o256-b48-open")
L, F = "linear_attention", "full_attention"
#: the catalog's ``config`` of Olmo-Hybrid-7B, as the model's config.json
#: gives it
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": [L, L, L, F] * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
OLMO = {
    "name": "olmo-tiny", "builder": "zoo.OlmoHybrid",
    "reference": "olmo_hybrid", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "layer_types": [L, L, L, F], "linear_num_key_heads": 2,
    "linear_num_value_heads": 2, "linear_key_head_dim": 32,
    "linear_value_head_dim": 64, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6,
    "vocab_size": 211, "max_position_embeddings": 64, "kv_block_size": 8,
    "param_dtype": "float32", "kv_dtype": "float32",
    "state_dtype": "float32", "control": "float8_e4m3fn",
    "limits": {"logit_gap_max": 1e-4},
}
TINY_MIX = dict(tiny.MIXES["tiny-open"], buckets="batch=8;seq=32",
                warm_prompt_lengths=[32], trace_after_s=0.2,
                trace_seconds=1.0)
NEW = ("gdn_step_roofline.olmoh", "gdn_chunk_roofline.olmoh",
       "decode_step_roofline.olmoh")


# ------------------------------------------------------- the shipped files
def _shipped():
    return manifest.Cell(manifest.load_manifest(), CELL).cfg


def test_the_configuration_holds_every_published_key():
    man = manifest.load_manifest()
    cell = manifest.Cell(man, CELL)
    cfg = cell.cfg
    assert (cell.spec["config"], cell.spec["traffic"], cell.chips) == \
        (CONFIG, MIX, 1)
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    cut = ["num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert entry["reduced"] == cfg["reduced"] == cut
    assert sorted(k for k, v in PUBLISHED.items() if cfg.get(k) != v) == \
        sorted(cut)
    for key in cut:
        assert cfg["published_" + key] == PUBLISHED[key]
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"]) == \
        (16, 512)
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:16]
    for key in ("deployment", "assumed", "control", "limits", "kv_block_size",
                "param_dtype", "kv_dtype", "state_dtype"):
        assert cfg[key]
    assert {"rope_theta", "linear_attention", "full_attention", "block",
            "weights", "numbers"} <= set(cfg["assumed"])


def test_the_mix_is_the_cells():
    mix = manifest.Cell(manifest.load_manifest(), CELL).mix
    want = {"kind": "open_loop", "clients": 256, "queue_limit": 256,
            "max_wait_ms": 5.0, "grace_s": 60, "check_requests": 8,
            "trace_after_s": 1.0, "trace_seconds": 10.0,
            "max_new_tokens": 256, "buckets": "batch=48;seq=256",
            "warm_prompt_lengths": [200],
            "prompt_tokens": {"dist": "lognormal", "median": 96,
                              "sigma": 0.6, "min": 32, "max": 256}}
    assert {k: mix[k] for k in want} == want
    others = {manifest.Cell(manifest.load_manifest(), w).mix["trace_seed"]
              for w in ("kimiL-chat-open", "glm47f-chat-open",
                        "jamba2-chat-open")}
    assert mix["trace_seed"] not in others and "shuffle_block" not in mix
    assert mix["rate_per_s"] == int(mix["rate_per_s"])  # a whole request a s
    assert 256 + 256 <= _shipped()["max_position_embeddings"]


def test_the_cell_reports_what_the_accepted_harness_admits():
    """The cell is appended to the accepted lists of the serving cells but
    two (``test_chipbench_turnaround.py`` holds the workloads of
    ``sched_turnaround_ms.open`` and ``decode_drain_ms.open`` exactly). The
    three new readers are files; a manifest that lists one lists it for
    this cell alone, moving its p90 (PERF.md 7.33)."""
    man = manifest.load_manifest()
    cell = manifest.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end()} == \
        {"serve_latency_p90_s", "setup_s"}
    accepted = {
        "gen_lateness_p95_ms", "sched_queue_wait_p50_s.open",
        "sched_batch_occupancy.open", "decode_step_device_ms.open",
        "decode_launch_gap_ms_p50.open", "device_idle_share.open",
        "prefill_device_ms.open", "serve_step_mfu.kimi",
        "prefill_roofline.glm47f", "compiles_in_window",
        "setup_cache_hit_share"}
    got = {m["name"] for m in cell.per_layer()}
    assert accepted <= got and got - accepted <= set(NEW)
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert os.path.isfile(os.path.join(manifest.HERE, "metrics",
                                           name + ".py"))
        if name in by_name:
            m = by_name[name]
            assert (m["unit"], m["better"], m["source"], m["moves"],
                    m["workloads"]) == ("%", "higher", "device_trace",
                                        "serve_latency_p90_s", [CELL])
    for name, m in by_name.items():
        if name.endswith(".jamba2") or name.startswith("moe_") \
                or name in ("decode_step_roofline.kimi",
                            "sched_turnaround_ms.open",
                            "decode_drain_ms.open"):
            assert CELL not in m.get("workloads", ())


# ------------------------------------------------------ the model's counts
LINEAR = 2 * 3840 * 2880 + 2 * 3840 * 5760 + 5760 * 3840 + 2 * 3840 * 30
LINEAR_SMALL = 4 * (2880 + 2880 + 5760) + 30 + 30 + 192
FULL = 4 * 3840 * 3840
FFN = 3 * 3840 * 11008
HEAD = 3840 * 100352


def test_the_cut_is_depth_only_by_hand_arithmetic():
    ref = manifest.module_from("reference", "olmo_hybrid")
    d = ref._dims(_shipped())
    count = lambda s: sum(math.prod(v) for v in s.values())
    linear, full = count(ref._layer_shapes(d, 0)), count(ref._layer_shapes(d,
                                                                           3))
    assert round((LINEAR + LINEAR_SMALL) / 1e6, 2) == 88.75
    assert linear == LINEAR + LINEAR_SMALL + FFN + 2 * 3840
    assert full == FULL + 2 * 3840 + FFN + 2 * 3840
    assert (round(linear / 1e6, 2), round(full / 1e6, 2),
            round(FFN / 1e6, 2)) == (215.57, 185.81, 126.81)
    layers = [count(ref._layer_shapes(d, i)) for i in range(16)]
    assert [i for i, n in enumerate(layers) if n == full] == [3, 7, 11, 15]
    total = sum(layers) + 2 * HEAD + 3840
    assert round(total / 1e6, 1) == 4100.8
    assert round(2 * total / 1e9, 2) == 8.20
    assert round(2 * total / 16.909e9 * 100, 1) == 48.5


def test_request_and_prefill_flops_against_a_hand_count():
    ref = manifest.module_from("reference", "olmo_hybrid")
    cfg = _shipped()
    products = 2 * (12 * (LINEAR + FFN) + 4 * (FULL + FFN))
    recurrence = 12 * (9 * 30 * 96 * 192 + 2 * 4 * 30 * (96 + 96 + 192))
    n, new = 100 + 256 - 1, 256
    want = n * (products + recurrence) \
        + 4 * 4 * 30 * 128 * (n * (n + 1) // 2) + new * 2 * HEAD
    assert ref.request_flops(cfg, 100, 256) == pytest.approx(want, rel=1e-12)
    chunk = 2 * 64 * 64 * 96 + 64 * 64 * 288 + 6 * 64 * 96 * 192 \
        + 64 * 64 * 192
    want = 48 * 256 * products + 48 * 4 * 12 * 30 * chunk \
        + 48 * 4 * 4 * 30 * 128 * (256 * 257 // 2) + 48 * 2 * HEAD
    assert ref.prefill_flops(cfg, 48, 256) == pytest.approx(want, rel=1e-12)
    assert round(want / 1e12) == 83          # about 83 TFLOP a prefill


def test_the_bytes_against_a_hand_count():
    ref = manifest.module_from("reference", "olmo_hybrid")
    cfg = _shipped()
    state = 4 * (30 * 96 * 192 + 3 * 30 * (96 + 96 + 192))
    assert state == 2350080                       # 2.35 MB a slot a layer
    weights = 2 * (12 * (LINEAR + FFN) + 4 * (FULL + FFN) + HEAD)
    kv = 48 * 224 * 4 * 2 * 2 * 30 * 128
    assert (round(weights / 1e9, 2), round(kv / 1e9, 2)) == (7.43, 0.66)
    assert round(48 * 12 * 2 * 4 * 30 * 96 * 192 / 1e9, 2) == 2.55
    want = weights + 48 * 12 * 2 * state + kv
    assert ref.decode_step_bytes(cfg, 48, 48 * 224) == want
    assert round(want / 819e9 * 1e3, 1) == 13.2
    assert ref.gdn_step_bytes(cfg, 40) == 4 * 40 * 30 * (
        2 * 96 * 192 + 2 * 96 + 2 + 2 * 192)
    flops, moved = ref.gdn_chunk_work(cfg, 48, 150)
    assert flops == 150 * 30 * (2 * 64 * 64 * 96 + 64 * 64 * 288
                                + 6 * 64 * 96 * 192 + 64 * 64 * 192)
    assert moved == 4 * (150 * 64 * 30 * (2 * 96 + 2 * 192 + 2)
                         + 48 * 30 * 96 * 192)
    # the memory: weights, 49 state slots, a K/V pool of 48 x 512
    slots = 49 * 12 * state
    pool = 48 * 512 * 4 * 2 * 2 * 30 * 128
    assert (round(slots / 1e9, 2), round(pool / 1e9, 2)) == (1.38, 1.51)


# ---------------------------------------------- the readers on a made-up run
def _run(**more):
    out = {"cfg": dict(OLMO), "mix": dict(TINY_MIX), "seconds": 2.0,
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
    assert read(_run(counters={"dl4j_serving_batches_total": 3.0},
                     trace={"module_s": {}, "module_n": {}, "op_s": {}})) \
        is None
    assert read(_run(counters={
        "dl4j_serving_batches_total": 0.0,
        "dl4j_serving_gdn_decode_states_live_total": 0.0,
        "dl4j_serving_gdn_decode_states_declared_total": 0.0,
        "dl4j_serving_gdn_prefill_chunks_live_total": 0.0,
        "dl4j_serving_prefill_launches_total": 0.0}, trace=_trace())) is None


DONE = [{"status": 200, "prompt": [1] * 10, "tokens": [2] * 6, "done": 1.0}
        for _ in range(12)]
COUNTERS = {"dl4j_serving_batches_total": 3.0,
            "dl4j_serving_completed_total": 12.0,
            "dl4j_serving_prefill_launches_total": 3.0,
            "dl4j_serving_gdn_prefill_chunks_live_total": 3.0 * 5,
            "dl4j_serving_gdn_decode_states_live_total": 3.0 * 4 * 20,
            "dl4j_serving_gdn_decode_states_declared_total": 3.0 * 8 * 20}


def test_the_kernel_readers_need_their_op_in_the_trace():
    for name in NEW[:2]:
        read = manifest.module_from("metrics", name).read
        assert read(_run(requests=DONE, counters=COUNTERS,
                         trace=_trace(**{"fusion.3 f32[8,64]": 1.0}))) is None


def test_the_new_readers_on_a_made_up_run():
    ref = manifest.module_from("reference", "olmo_hybrid")
    made = _run(requests=DONE, counters=COUNTERS, trace=_trace(**{
        "kda_decode.3 f32[8,1,1,128]": 0.0004,
        "kda_decode.4 f32[8,1,1,128]": 0.0002,
        "kda_prefill.1 f32[8,2,64,64]": 0.0009, "fusion.1 f32[8,64]": 0.5}))
    read = lambda n: manifest.module_from("metrics", n).read(made)
    # 3 linear layers x 20 launches share 0.6 ms; 4 of the 8 rows are live
    least = ref.gdn_step_bytes(OLMO, 4) / 819e9
    assert read(NEW[0]) == pytest.approx(least / (0.0006 / 60) * 100)
    # 3 layers x 3 launches share 0.9 ms; 5 live chunks in 4 rows a launch
    flops, moved = ref.gdn_chunk_work(OLMO, 4, 5)
    least = max(flops / 197e12, moved / 819e9)
    assert read(NEW[1]) == pytest.approx(least / (0.0009 / 9) * 100)
    # 4 streams a batch of 10 + 3 tokens each; a step is 1 ms
    least = ref.decode_step_bytes(OLMO, 4, 4 * 13) / 819e9
    assert read(NEW[2]) == pytest.approx(least / 0.001 * 100)
    for name in NEW:
        assert 0 < read(name) <= 100


# ------------------------------------------------- the tiny cell, end to end
@pytest.fixture
def tree(tmp_path, monkeypatch):
    """The tiny tree with the Olmo cell added as a file and entries, listed
    wherever ``olmoh-chat-open`` is."""
    tiny.quiet_cache(monkeypatch)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / ".out"))
    man = tiny.tiny_tree(tmp_path, monkeypatch)
    path = "chipbench/configs/olmo-tiny.json"
    with open(os.path.join(manifest.ROOT, path), "w") as f:
        json.dump(OLMO, f)
    man["configs"].append({"name": "olmo-tiny", "source": "test",
                           "file": path, "reduced": [], "why": "test"})
    with open(os.path.join(manifest.HERE, "traffic", "olmo-open.json"),
              "w") as f:
        json.dump(TINY_MIX, f)
    man["workloads"].append({"name": "olmo-tiny-open",
                             "config": "olmo-tiny", "traffic": "olmo-open",
                             "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("olmo-tiny-open")
    return man


def test_a_sound_open_loop_run_traced_reports_what_it_can_read(tree,
                                                              monkeypatch):
    # a CPU trace holds no TPU plane: the reduction reads the recorded one
    monkeypatch.setattr(trace, "reduce_logdir", lambda d: trace.reduce_trace(
        trace.read_planes(SMALL)))
    res = run.measure(manifest.Cell(tree, "olmo-tiny-open"), 2 ** 31 + 41,
                      1.5, True, tiny.DEVICE)
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


# ----------------------------------------------------- the planted faults
NEW_TOKENS = 24


@pytest.fixture(scope="module")
def sound():
    """(reference, weights, builder, prompts) of the tiny model, its weights
    made so that every term moves a logit of the 4 x 24 served tokens: a
    slow decay, beta near its top of 2 (every transition's eigenvalue along
    k near -1), and the norms' scales away from 1."""
    ref = manifest.module_from("reference", "olmo_hybrid")
    builder = manifest.module_from("builders", "zoo.OlmoHybrid")
    w = ref.make_weights(5, OLMO)
    for p in w["layers"]:
        p.update(norm1=p["norm1"] * 3, norm2=p["norm2"] * 2)
        if "Wa" in p:
            p.update(A_log=p["A_log"] - 3.0, Wb=p["Wb"] * 20.0,
                     o_norm=p["o_norm"] * 3)
        else:
            p.update(q_norm=p["q_norm"] * 4, k_norm=p["k_norm"] * 4)
    prompts = [[3 + i] * (9 + 4 * i) for i in range(4)]
    return ref, w, builder, prompts


def _served(sound):
    """What the program as it now stands serves."""
    from deeplearning4j_tpu.serving.generate import Generator

    ref, w, builder, prompts = sound
    net = builder.build(OLMO)
    builder.load(net, w)
    gen = Generator(net, max_length=64, batch_buckets=(4,),
                    prefill_buckets=(32,), block_size=8)
    return [{"prompt": p, "tokens": t} for p, t in
            zip(prompts, gen.generate(prompts, max_new_tokens=NEW_TOKENS))]


def _gap(sound):
    ref, w, _, _ = sound
    return float(serve.served_gaps(ref, w, OLMO, _served(sound)).max())


def test_the_program_as_it_stands_is_correct_and_the_fp8_control_is_not(
        sound):
    """The control of ``chipbench.calibrate``: the reference computed with
    fp8 operands put in the program's place fails ``logit_gap_max``."""
    import jax.numpy as jnp

    from chipbench import checks

    ref, w, _, _ = sound
    ok, mix = _served(sound), dict(TINY_MIX, max_new_tokens=NEW_TOKENS)
    numbers = serve.judge(ref, w, OLMO, mix, 5, ok)
    assert checks.verdict(numbers) is True, numbers
    numbers = serve.judge(ref, w, OLMO, mix, 5, ok,
                          control_dtype=jnp.dtype(OLMO["control"]))
    assert checks.verdict(numbers) is False
    assert numbers[0]["value"] > OLMO["limits"]["logit_gap_max"]


FAULTS = ["beta held in (0, 1): no negative eigenvalues",
          "the pre-norm block in place of the post-norm one",
          "QK-norm left out", "the value norm before the gate left out",
          "the state carried in bfloat16"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(sound, monkeypatch, fault):
    import dataclasses

    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import decoder
    from deeplearning4j_tpu.nn.decoder import HybridDecoderBlock as Block
    from deeplearning4j_tpu.ops import kda

    if fault == FAULTS[0]:
        monkeypatch.setitem(decoder._DELTA_RULES, "gdn", decoder._DELTA_RULES[
            "gdn"]._replace(beta_top=1.0))
    elif fault == FAULTS[1]:
        monkeypatch.setattr(Block, "_normed_in", lambda self, p, x, name:
                            decoder.rms_norm(x, p[name], self.eps))
        monkeypatch.setattr(Block, "_normed_out", lambda self, p, y, name: y)
    elif fault == FAULTS[2]:
        real = Block._gqa_qkv
        monkeypatch.setattr(Block, "_gqa_qkv", lambda self, p, h: real(
            dataclasses.replace(self, qk_norm=False), p, h))
    elif fault == FAULTS[3]:
        def no_norm(self, p, o, gate):
            o = o * p["o_norm"].astype(jnp.float32) * gate
            return decoder._mm(o.reshape(*o.shape[:2], -1), p["Wo"])

        monkeypatch.setattr(Block, "_kda_out", no_norm)
    else:
        real_chunked, real_step = kda.kda_chunked, kda.kda_step_paged
        low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

        def chunked(*a, **kw):
            o, s = real_chunked(*a, **kw)
            return o, low(s)

        def step(q, k, v, g, beta, pool, *a):
            o, pool = real_step(q, k, v, g, beta, low(pool), *a)
            return o, low(pool)

        monkeypatch.setattr(kda, "kda_chunked", chunked)
        monkeypatch.setattr(kda, "kda_step_paged", step)
    assert _gap(sound) > 1e-4
