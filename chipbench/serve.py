"""Serving cells: the program's ``ModelServer`` in this process (it owns the
chip), the load generator in a child that never imports JAX, requests over
HTTP to ``POST /v1/models/<id>/generate``.

``closed_loop``: ``clients`` callers, each sending its next request when the
last returns. ``open_loop``: requests sent when they are due, at the mix's
fixed rate, whatever the server does.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np

from chipbench import checks, traffic
from chipbench.manifest import ROOT, module_from

MODEL_ID = "cell"
START_DELAY_S = 1.0      # child start-up, before the first request is due


def _get(url: str) -> str:
    with urllib.request.urlopen(url, timeout=60) as r:
        return r.read().decode()


def prometheus(text: str) -> dict:
    """``name{labels} value`` lines -> {name: sum over the label sets}."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def start_server(cfg: dict, mix: dict, weights, builder):
    """The program, set up as an operator would: a paged ``ServingModel``
    behind ``ModelRouter`` and ``ModelServer``, warmed for the buckets this
    mix reaches and no others."""
    from deeplearning4j_tpu.serving import (ModelRouter, ModelServer,
                                            ServingModel)

    t0 = time.perf_counter()
    net = builder.build(cfg)
    builder.load(net, weights)
    print(f"chipbench: model built in {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)
    model = ServingModel(net, MODEL_ID, kind="generate", paged=True,
                         block_size=int(cfg["kv_block_size"]),
                         max_length=int(cfg["max_position_embeddings"]),
                         bucketing=mix["buckets"])
    router = ModelRouter(name="chipbench")
    router.register(model, max_wait_ms=float(mix["max_wait_ms"]),
                    queue_limit=int(mix["queue_limit"]))
    # warm through generate() itself, one short batch per (batch bucket,
    # prompt bucket) this mix reaches: Generator.warmup() leaves the small
    # programs of the decode loop (argmax, position add, key split) cold,
    # and they would compile inside the window
    for b in model.policy.batch_buckets:
        for t in mix["warm_prompt_lengths"]:
            model.generator.generate([[1] * int(t)] * int(b),
                                     max_new_tokens=2)
    model.warmed = True
    print(f"chipbench: warmed in {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)
    server = ModelServer(router, port=0).start(warmup=False)
    return server, model


def drive(url_base: str, mix: dict, reqs: list, mode: str, seconds: float,
          on_start=None, poll=None) -> dict:
    """One window of traffic from the child process against a running
    server; waits for every answer. Returns the child's rows (with their
    prompts), the server's counters over the window and its last request
    records. ``on_start`` is called when the window opens, ``poll`` about
    every 50 ms while it is open."""
    url = f"{url_base}/v1/models/{MODEL_ID}/generate"
    before = prometheus(_get(f"{url_base}/metrics"))
    child = subprocess.Popen(
        [sys.executable, "-m", "chipbench.loadgen"], cwd=ROOT,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        start = time.time() + START_DELAY_S
        job = {"url": url, "mode": "closed" if mode == "closed_loop"
               else "open", "start": start, "seconds": seconds,
               "clients": int(mix["clients"]),
               "grace_s": float(mix["grace_s"]), "requests": reqs}
        child.stdin.write(json.dumps(job).encode())
        child.stdin.close()
        time.sleep(max(0.0, start - time.time()))
        if on_start is not None:
            on_start()
        while time.time() - start < seconds:
            if poll is not None:
                poll()
            time.sleep(0.05)
        out = child.stdout.read()
        child.wait(timeout=seconds + float(mix["grace_s"]) + 30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    result = json.loads(out)
    after = prometheus(_get(f"{url_base}/metrics"))
    flight = json.loads(_get(
        f"{url_base}/v1/models/{MODEL_ID}/debug/requests?last=256"))
    for r in result["requests"]:
        r["prompt"] = reqs[r["i"]]["prompt"]
    never = result.get("never_sent", 0) if mode == "open_loop" else 0
    return {"rows": result["requests"], "never_sent": never,
            "counters": {k: after[k] - before.get(k, 0.0) for k in after},
            "flight": flight.get("requests", [])}


def warm_request(url_base: str, req: dict) -> None:
    """One request end to end before the clock starts: the HTTP path and
    the scheduler's thread are then warm."""
    body = json.dumps({"prompt_tokens": [req["prompt"]],
                       "max_new_tokens": req["max_new_tokens"]}).encode()
    urllib.request.urlopen(urllib.request.Request(
        f"{url_base}/v1/models/{MODEL_ID}/generate", data=body,
        headers={"Content-Type": "application/json"}), timeout=300).read()


def judge(ref, weights, cfg: dict, mix: dict, seed: int, ok: list,
          control_dtype=None) -> list:
    """The numbers that decide ``correct`` for a served model (checks.py),
    from the finished requests ``ok``."""
    want_new, vocab = int(mix["max_new_tokens"]), cfg["vocab_size"]
    bad = sum(1 for r in ok if len(r["tokens"]) != want_new
              or not all(0 <= t < vocab for t in r["tokens"]))
    sample = traffic.sample_for_check(
        [r for r in ok if len(r["tokens"]) == want_new], seed,
        int(mix["check_requests"]))
    gaps = served_gaps(ref, weights, cfg, sample,
                       control_dtype=control_dtype)
    return checks.serving_numbers(gaps, bad, cfg["limits"])


def run(ctx, mode: str, planted=None) -> dict:
    cfg, mix, seed = ctx.cell.cfg, ctx.cell.mix, ctx.seed
    ref = module_from("reference", cfg["reference"])
    builder = module_from("builders", cfg["builder"])
    weights = ref.make_weights(seed, cfg)
    server, model = start_server(cfg, mix, weights, builder)
    if planted is not None:
        planted(model)
    tracer = ctx.tracer()
    try:
        reqs = traffic.requests(mix, cfg, seed, ctx.seconds)
        warm_request(server.url, reqs[0])
        with tracer:
            got = drive(server.url, mix, reqs, mode, ctx.seconds,
                        on_start=ctx.mark_setup_done, poll=tracer.poll)
    finally:
        server.stop()
    peak = ctx.memory_peak_bytes()

    # ---- what the window did
    seconds, rows = ctx.seconds, got["rows"]
    ok = [r for r in rows if r["status"] == 200]
    failed = len(rows) - len(ok) + got["never_sent"]
    in_window = [r for r in ok if r["done"] <= seconds]
    tokens = sum(len(r["prompt"]) + len(r["tokens"]) for r in in_window)
    lat = [r["done"] - r["due"] for r in ok] + [seconds] * failed
    e2e = {"serve_tokens_per_s": tokens / seconds}
    if lat:
        e2e["serve_latency_p90_s"] = float(np.quantile(lat, 0.9))
    if len(in_window) > 1:   # information: a stall shows here, a slow run not
        e2e["completion_gap_max_s"] = float(np.max(np.diff(np.sort(
            [r["done"] for r in in_window]))))

    # ---- free the program's state, then ask the reference
    model.generator.pool.pools = None
    del server, model
    gc.collect()
    numbers = judge(ref, weights, cfg, mix, seed, ok)
    return {
        "attempted": len(rows) + got["never_sent"], "failed": failed,
        "numbers": numbers,
        "memory_peak_bytes": peak,
        "end_to_end": e2e,
        "run": {"requests": rows, "seconds": seconds,
                "counters": got["counters"], "flight": got["flight"],
                "tokens_in_window": tokens,
                "flops_in_window": sum(
                    _request_flops(cfg, r) for r in in_window)},
    }


def _request_flops(cfg, r):
    from chipbench import work

    return work.decoder_request_flops(cfg, len(r["prompt"]),
                                      len(r["tokens"]))


def check_inputs(cfg: dict, sample: list):
    """Pad the sampled requests (prompt + served tokens) to one width:
    (tokens (N, T), positions (N, P), served (N, P))."""
    width = int(cfg["max_position_embeddings"])
    new = len(sample[0]["tokens"])
    toks = np.zeros((len(sample), width), np.int32)
    pos = np.zeros((len(sample), new), np.int32)
    for i, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"][:-1])
        toks[i, :len(seq)] = seq
        pos[i] = len(r["prompt"]) - 1 + np.arange(new)
    served = np.asarray([r["tokens"] for r in sample], np.int32)
    return toks, pos, served


def served_gaps(ref, weights, cfg, sample, rows_per_block: int = 8,
                control_dtype=None):
    """For every served token of the sample: how far its logit in the
    reference's full forward over prompt + served tokens lies below the
    reference's best. With ``control_dtype`` the token judged is instead the
    one the reference computed in that lower precision puts first (the
    control; it need not decode)."""
    import jax
    import jax.numpy as jnp

    if not sample:
        return np.zeros((0,))
    toks, pos, served = check_inputs(cfg, sample)
    heads = int(cfg["num_attention_heads"])
    gaps = []
    for i in range(0, len(sample), rows_per_block):
        t, p = toks[i:i + rows_per_block], pos[i:i + rows_per_block]
        pad = rows_per_block - len(t)
        if pad:   # one compiled shape for every block
            t = np.concatenate([t, np.repeat(t[-1:], pad, 0)])
            p = np.concatenate([p, np.repeat(p[-1:], pad, 0)])
        with jax.default_matmul_precision("highest"):
            lg = ref.logits_at(weights, jnp.asarray(t), jnp.asarray(p),
                               n_heads=heads)
        if control_dtype is None:
            pick = jnp.asarray(np.concatenate(
                [served[i:i + rows_per_block],
                 np.zeros((pad, served.shape[1]), np.int32)]))
        else:
            pick = jnp.argmax(ref.logits_at(
                weights, jnp.asarray(t), jnp.asarray(p), n_heads=heads,
                dtype=control_dtype), axis=-1)
        gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, pick[..., None], axis=-1)[..., 0]
        gaps.append(np.asarray(gap)[:rows_per_block - pad])
    return np.concatenate(gaps).ravel()
