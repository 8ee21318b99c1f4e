"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # on a TPU host; exits 0 within 20 min

Drives the two normal entry points once on the accelerator, through the
public API, at the full width of models the repo ships, in ONE process (a
chip belongs to one process; nothing this script starts needs the device):

1. trainer — ``zoo.ResNet50`` 224x224 bf16, B=256, ``net.fit(iterator)``;
2. server — ``zoo.Bert.base(causal=True)`` (12 layers, hidden 768, vocab
   30,522) as a paged ``ServingModel`` behind ``ModelRouter`` /
   ``ModelServer``, answering ``POST /v1/models/<id>/generate`` over HTTP;
3. kernels — every Pallas kernel the dispatch seams can reach, compiled by
   Mosaic (never interpreted) and compared with its exact path;
4. with four or more devices, ``ParallelWrapper(net).fit`` at global batch
   1,024 with the layout asserted from ``addressable_shards``.

Weights are random, from fixed seeds. The first thing printed is what JAX
found; unless that is a TPU the script exits non-zero before any leg. A leg
that fails raises, so the exit code cannot stay 0. The seconds printed are
information for whoever reads the log, not benchmark metrics. The last line
of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import gc
import json
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

# bf16 has 8 bits of mantissa; a kernel and its exact path round at different
# points, so agreement is judged against the largest reference value. The
# worst case measured on v5e is 2.1e-2 (flash causal dq, PR 21); a wrong
# kernel is off by the order of the values themselves.
BF16_REL_TOL = 4e-2
#: the KDA prefill against the recurrence: one bfloat16 rounding (2**-8) of
#: the largest value, twice over (docs/KERNELS.md)
KDA_REL_TOL = 2 ** -7
KDA_STEP_REL_TOL = 1e-5     # the decode step: float32, no matrix product
SSM_REL_TOL = 1e-5          # the state-space kernels: float32, element-wise


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    say(f"  ok  {what}")


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-6))


# --------------------------------------------------------------- trainer
def _resnet(image: int, classes: int):
    from deeplearning4j_tpu.zoo import ResNet50

    return ResNet50(num_classes=classes, input_shape=(image, image, 3),
                    compute_dtype="bfloat16").init()


def _resnet_batch(batch: int, image: int, classes: int):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, image, image, 3)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, batch)]
    return x, y


def _train_mode_loss(net, x, y) -> float:
    """Cross-entropy of the training-mode forward (batch statistics) — what
    the first ``fit()`` step reports before it updates anything."""
    p = np.asarray(net.output(x, train=True), np.float32)
    return float(-np.mean(np.sum(y * np.log(np.maximum(p, 1e-30)), axis=-1)))


def leg_trainer(batch: int = 256, image: int = 224, classes: int = 1000,
                steps: int = 6) -> None:
    """The sizes are the r05 flagship's; smaller ones are for debugging the
    script itself off the chip."""
    import jax

    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.nn.listeners import CollectScoresListener

    say(f"leg trainer: zoo.ResNet50 {image}x{image} classes={classes} bf16 "
        f"B={batch}, fit()")
    net = _resnet(image, classes)
    x, y = _resnet_batch(batch, image, classes)
    ref = _train_mode_loss(net, x, y)
    scores = CollectScoresListener()
    net.set_listeners(scores)
    it = ArrayDataSetIterator(x, y, batch=batch)
    t0 = time.perf_counter()
    net.fit(it)                      # first step: trace + compile + run
    jax.block_until_ready(net.params)
    t1 = time.perf_counter()
    net.fit(it, epochs=steps - 1)    # the same batch again, compiled
    jax.block_until_ready(net.params)
    t2 = time.perf_counter()
    losses = [s for _, s in scores.scores]
    say(f"  losses {' '.join(f'{v:.4f}' for v in losses)}")
    say(f"  first step incl. compile {t1 - t0:.1f} s; then "
        f"{(t2 - t1) / (steps - 1) * 1e3:.0f} ms/step wall with a host fetch "
        f"and a {x.nbytes >> 20} MiB input copy per step (info, not a "
        "metric)")
    require(len(losses) == steps, f"{steps} fit() steps reported a loss")
    require(all(np.isfinite(losses)), "loss finite at every step")
    require(losses[-1] < losses[0],
            f"loss fell: {losses[0]:.4f} -> {losses[-1]:.4f}")
    require(abs(losses[0] - ref) <= BF16_REL_TOL * ref,
            f"first-step loss {losses[0]:.4f} == training-mode forward "
            f"cross-entropy {ref:.4f}")


# ---------------------------------------------------------------- server
MODEL_ID = "bert-base-decoder"
MAX_NEW = 32


def _post(url: str, obj: dict):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=300) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:300]}


def _get(url: str) -> str:
    with urllib.request.urlopen(url, timeout=60) as r:
        return r.read().decode()


def _recompiles(base: str) -> list:
    """Every ``serving.recompiles_total`` series on /metrics (the scheduler
    adds each batch's trace count to it; the series exists once a batch
    has run)."""
    return [float(line.rsplit(" ", 1)[1])
            for line in _get(f"{base}/metrics").splitlines()
            if line.startswith("dl4j_serving_recompiles_total")]


def leg_server(**bert_kw) -> None:
    """``bert_kw`` overrides BERT-base's width and depth — for debugging the
    script itself off the chip only."""
    from deeplearning4j_tpu.serving import (ModelRouter, ModelServer,
                                            ServingModel)
    from deeplearning4j_tpu.zoo.bert import Bert

    bert = Bert.base(causal=True, task="mlm", max_length=1024,
                     hidden_dropout=0.0, **bert_kw)
    say(f"leg server: zoo.Bert causal mlm, {bert.n_layers} layers, hidden "
        f"{bert.hidden_size}, {bert.n_heads} heads, vocab {bert.vocab_size}, "
        f"max_length {bert.max_length}, paged KV, over HTTP")
    net = bert.init()
    model = ServingModel(net, MODEL_ID, kind="generate", paged=True,
                         bucketing="batch=1,4;seq=64,256")
    router = ModelRouter(name="chip-smoke")
    router.register(model, max_wait_ms=5.0)
    t0 = time.perf_counter()
    server = ModelServer(router, port=0).start(warmup=True)
    try:
        say(f"  warm-up (every prefill and decode bucket) "
            f"{time.perf_counter() - t0:.1f} s (info)")
        url = f"{server.url}/v1/models/{MODEL_ID}/generate"
        rec0 = sum(_recompiles(server.url))
        rng = np.random.default_rng(1)
        # lengths on both sides of the 16-token block edge and of every
        # prefill bucket (64, 256, then max_length)
        lengths = (5, 16, 17, 63, 65, 250, 300)
        prompts = [[int(t) for t in rng.integers(1, bert.vocab_size, size=n)]
                   for n in lengths]
        results = [None] * len(prompts)

        def fire(i):
            results[i] = _post(url, {"prompt_tokens": [prompts[i]],
                                     "max_new_tokens": MAX_NEW})

        t0 = time.perf_counter()
        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        dt = time.perf_counter() - t0
        require(all(r is not None and r[0] == 200 for r in results),
                f"{len(prompts)} concurrent generate requests answered 200 "
                f"(prompt lengths {lengths}): "
                f"{[r and r[0] for r in results]}")
        toks = [r[1]["tokens"][0] for r in results]
        require(all(len(t) == MAX_NEW for t in toks),
                f"every response holds {MAX_NEW} new tokens")
        require(all(0 <= int(v) < bert.vocab_size for t in toks for v in t),
                "every token is inside the vocabulary")
        say(f"  burst of {len(prompts)} took {dt:.1f} s (info)")
        # the same prompt alone, twice: one executable, one input, so greedy
        # decoding must repeat itself token for token
        a = _post(url, {"prompt_tokens": [prompts[2]],
                        "max_new_tokens": MAX_NEW})
        b = _post(url, {"prompt_tokens": [prompts[2]],
                        "max_new_tokens": MAX_NEW})
        require(a[0] == 200 and b[0] == 200
                and a[1]["tokens"] == b[1]["tokens"],
                "same prompt twice gives the same greedy tokens")
        rec = _recompiles(server.url)
        require(rec and sum(rec) == rec0,
                f"serving.recompiles_total did not move after warm-up "
                f"({len(rec)} series, stayed at {rec0:g})")
        pool = json.loads(_get(f"{server.url}/v1/models"))[
            "models"][MODEL_ID]["kv_pool"]
        require(pool["streams"] == 0
                and pool["blocks_free"] == pool["blocks_total"],
                f"pool empty at the end: {pool['blocks_free']}/"
                f"{pool['blocks_total']} blocks free, "
                f"{pool['pool_bytes']} bytes")
        ok, detail = model.generator.pool.conservation()
        require(ok, f"block accounting conserved ({detail})")
    finally:
        server.stop()
    say("  ok  server.stop() returned")


# --------------------------------------------------------------- kernels
def _compile_on_chip(fn, *args):
    """Compile ``fn`` for this backend and return (compiled, uses_mosaic)."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, "tpu_custom_call" in compiled.as_text()


def _kernel_case(name: str, kernel_fn, exact_fn, *args) -> None:
    """value + gradients of ``kernel_fn`` against ``exact_fn`` on ``args``;
    the kernel program must contain a Mosaic custom call, the exact one must
    not."""
    import jax
    import jax.numpy as jnp

    def vg(fn):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.value_and_grad(loss, argnums=tuple(range(len(args))),
                                  has_aux=True)

    k_exec, k_mosaic = _compile_on_chip(vg(kernel_fn), *args)
    e_exec, e_mosaic = _compile_on_chip(vg(exact_fn), *args)
    require(k_mosaic and not e_mosaic,
            f"{name}: kernel program has tpu_custom_call, exact has none")
    (_, k_out), k_grads = k_exec(*args)
    (_, e_out), e_grads = e_exec(*args)
    errs = [rel_err(k_out, e_out)] + [rel_err(k, e)
                                      for k, e in zip(k_grads, e_grads)]
    require(all(np.isfinite(np.asarray(k_out, np.float32)).ravel()),
            f"{name}: kernel output finite")
    require(max(errs) <= BF16_REL_TOL,
            f"{name}: value and {len(k_grads)} gradients match the exact "
            f"path, worst relative error {max(errs):.2e}")


def _kda_draws(seed: int, b: int, t: int, h: int, d: int):
    """q, k, v, g (b, t, h, d) and beta (b, t, h) as a KDA layer makes them:
    unit q and k after SiLU, log decays about -0.05, beta in (0, 1)."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    draw = lambda key: jax.nn.silu(jax.random.normal(key, (b, t, h, d)))
    q, k, v = unit(draw(ks[0])) * d ** -0.5, unit(draw(ks[1])), draw(ks[2])
    g = -jnp.exp(jax.random.normal(ks[3], (b, t, h, d)) * 0.5 - 3.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def _kda_prefill_case(b: int = 16, t: int = 1024, h: int = 32,
                      d: int = 128) -> None:
    """``kda_chunked`` as the serving path calls it on a TPU (one layer at
    ``kimiL-chat-open``'s prefill bucket): the kernel, against the token-by-
    token recurrence in float32 at ``highest``, with the lengths of a real
    batch (rows of a few hundred tokens beside padding rows of one) and with
    every row full. ``KDA_REL_TOL``: bfloat16 products, float32 state."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import kda

    q, k, v, g, beta = _kda_draws(3, b, t, h, d)
    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    ragged = np.ones((b,), np.int32)
    ragged[:9] = np.minimum([512, 612, 434, 300, 389, 282, 530, t, 381], t)

    def oracle(q, k, v, g, beta, s0, n):
        live = (jnp.arange(t)[None] < n[:, None]).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            return kda.kda_recurrent(q, k, v, g * live[..., None, None],
                                     beta * live[..., None], s0)

    args = (q, k, v, g, beta, s0)
    n0 = jnp.asarray(ragged)
    kernel, mosaic = _compile_on_chip(kda.kda_chunked, *args, n0)
    require(mosaic, "kda prefill: kda_chunked on a TPU is a Mosaic kernel")
    oracle = jax.jit(oracle)
    for name, lens in (("a batch's lengths", ragged),
                       ("every row full", np.full((b,), t, np.int32))):
        n = jnp.asarray(lens)
        (o, s), (o_r, s_r) = kernel(*args, n), oracle(*args, n)
        held = (jnp.arange(t)[None] < kda.live_chunks(n)[:, None]
                * kda.CHUNK)[..., None, None]
        require(not bool(jnp.any(jnp.where(held, 0.0, o))),
                f"kda prefill, {name}: o is 0 in every chunk behind a length")
        errs = (rel_err(o, jnp.where(held, o_r, 0.0)), rel_err(s, s_r))
        require(max(errs) <= KDA_REL_TOL,
                f"kda prefill, {name}: o and the state match kda_recurrent, "
                f"relative errors {errs[0]:.2e} {errs[1]:.2e}")


def _kda_decode_case(b: int = 16, h: int = 32, d: int = 128,
                     n_slots: int = 17) -> None:
    """``kda_step_paged`` as the serving path calls it on a TPU (one layer
    of ``kimiL-chat-open``'s decode step: 16 rows of one token, a pool of
    17 slots): the kernel, against ``kda_step`` on the gathered rows in
    float32 at ``highest``, with a batch's rows (live rows in permuted
    slots, a finished row that keeps its slot, padding rows on slot 0) and
    with every row live. Float32 on the vector unit: ``KDA_STEP_REL_TOL``.
    Every slot no live row names is the pool's, bit for bit."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import kda

    q, k, v, g, beta = _kda_draws(5, b, 1, h, d)
    pool = jax.random.normal(jax.random.PRNGKey(6), (n_slots, h, d, d))

    def oracle(q, k, v, g, beta, pool, slots):
        with jax.default_matmul_precision("highest"):
            return kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], pool[slots])

    args = (q, k, v, g, beta, pool)
    mixed = np.zeros((b,), np.int32)
    mixed[:9] = [9, 4, 16, 1, 7, 12, 3, 8, 5]
    mixed_live = np.arange(b) < 9
    mixed_live[4] = False                       # finished, keeps slot 7
    every = np.arange(1, b + 1, dtype=np.int32)
    kernel, mosaic = _compile_on_chip(
        kda.kda_step_paged, *args, jnp.asarray(mixed),
        jnp.asarray(mixed_live)[:, None])
    require(mosaic, "kda decode: kda_step_paged on a TPU is a Mosaic kernel")
    oracle = jax.jit(oracle)
    for name, slots, live in (("a batch's rows", mixed, mixed_live),
                              ("every row live", every, np.ones(b, bool))):
        o, new = kernel(*args, jnp.asarray(slots), jnp.asarray(live)[:, None])
        o_r, s_r = oracle(*args, jnp.asarray(slots))
        named = slots[live]
        errs = (rel_err(o[:, 0][live], o_r[live]),
                rel_err(new[named], s_r[live]))
        require(max(errs) <= KDA_STEP_REL_TOL,
                f"kda decode, {name}: o and the named slots match kda_step, "
                f"relative errors {errs[0]:.2e} {errs[1]:.2e}")
        rest = np.setdiff1d(np.arange(n_slots), named)
        require(bool(jnp.array_equal(new[rest], pool[rest])),
                f"kda decode, {name}: the {len(rest)} slots no live row "
                "names are bit-identical")


def _ssm_case(b: int = 64, t: int = 256, ch: int = 5120, n: int = 16) -> None:
    """``selective_scan`` and ``selective_step_paged`` as the serving path
    calls them on a TPU (one layer of ``jamba2-chat-open``: 64 rows x 256
    positions, 5,120 channels of 16 states, a pool of 65 slots): each
    kernel against the XLA form of the same float32 recurrence, with a
    batch's lengths and rows (a row of length 0, padding rows on slot 0, a
    finished row that keeps its slot). Element-wise float32 on the vector
    unit in the same order: ``SSM_REL_TOL``. Then the convolution's tail
    beside them (:func:`_conv_step_case`), at this cell's shape and at
    ``kimiL-chat-open``'s (16 rows, 3 x 12,288 channels, 17 slots)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import ssm

    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    x, z = (jax.random.normal(k, (b, t, ch)) for k in ks[:2])
    dt = jax.nn.softplus(jax.random.normal(ks[2], (b, t, ch)) - 3.0)
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1), (ch, n))
    bm, cm = (jax.random.normal(k, (b, t, n)) for k in ks[3:5])
    d, s0 = jnp.ones((ch,)), jnp.zeros((b, n, ch))
    lengths = jnp.asarray(np.r_[0, 1, 77, 256, np.random.default_rng(0)
                                .integers(32, 257, b - 4)], jnp.int32)
    args = (x, dt, a, bm, cm, d, s0, lengths, z)
    kernel, mosaic = _compile_on_chip(ssm.selective_scan, *args)
    require(mosaic, "ssm scan: selective_scan on a TPU is a Mosaic kernel")
    y, s = kernel(*args)
    y_r, s_r = jax.jit(ssm._selective_scan_xla)(
        x, dt, a.T, bm, cm, d, s0, lengths, z)
    errs = rel_err(y, y_r), rel_err(s, s_r)
    require(max(errs) <= SSM_REL_TOL and not bool(jnp.any(y[0])),
            f"ssm scan: y and the final state match the XLA form, relative "
            f"errors {errs[0]:.2e} {errs[1]:.2e}; a row of length 0 reads 0")
    pool = jax.random.normal(ks[5], (b + 1, n, ch)) * 0.1
    slots = np.zeros((b,), np.int32)
    slots[:40] = np.random.default_rng(1).permutation(b)[:40] + 1
    live = np.arange(b) < 40
    live[4] = False                             # finished, keeps its slot
    step = (x[:, :1], dt[:, :1], a, bm[:, :1], cm[:, :1], d, pool,
            jnp.asarray(slots), jnp.asarray(live)[:, None], z[:, :1])
    kernel, mosaic = _compile_on_chip(ssm.selective_step_paged, *step)
    require(mosaic, "ssm step: selective_step_paged on a TPU is a Mosaic "
            "kernel")
    y, new = kernel(*step)
    y_r, s_r = jax.jit(ssm._selective_scan_xla)(
        x[:, :1], dt[:, :1], a.T, bm[:, :1], cm[:, :1], d, pool[slots], None,
        z[:, :1])
    named = slots[live]
    errs = rel_err(y[live], y_r[live]), rel_err(new[named], s_r[live])
    require(max(errs) <= SSM_REL_TOL,
            f"ssm step: y and the named slots match the XLA form, relative "
            f"errors {errs[0]:.2e} {errs[1]:.2e}")
    rest = np.setdiff1d(np.arange(b + 1), named)
    require(bool(jnp.array_equal(new[rest], pool[rest])),
            f"ssm step: the {len(rest)} slots no live row names are "
            "bit-identical")
    _conv_step_case("jamba2-chat-open", ch, slots, live, (b + 1, 3 * ch))
    slots = np.zeros((16,), np.int32)
    slots[:10] = np.random.default_rng(2).permutation(16)[:10] + 1
    live = np.arange(16) < 10
    live[4] = False                             # finished, keeps its slot
    _conv_step_case("kimiL-chat-open", 12288, slots, live, (17, 3, 12288))


def _conv_step_case(cell: str, ch: int, slots, live, pool_shape) -> None:
    """``conv_step_paged`` as ``cell``'s decode step calls it on a TPU (one
    state layer: a row a slot of ``slots``, one token of ``ch`` channels, 4
    taps, the tail pool as the block's ``init_pool`` shapes it): the kernel
    against the XLA form (gather, ``causal_conv``, ``conv_tail``, scatter).
    The same float32 products and sums in the same order: bit-identical, on
    y of the live rows, on every named slot and on every slot no live row
    names."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import kda

    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    raw = jax.random.normal(ks[0], (len(slots), 1, ch))
    w = jax.random.normal(ks[1], (4, ch)) * 0.5
    pool = jax.random.normal(ks[2], pool_shape)
    args = (raw, w, pool, jnp.asarray(slots), jnp.asarray(live)[:, None])
    kernel, mosaic = _compile_on_chip(kda.conv_step_paged, *args)
    require(mosaic, f"conv step, {cell}: conv_step_paged on a TPU is a "
            "Mosaic kernel")
    (y, new), (y_r, new_r) = kernel(*args), jax.jit(kda._conv_step_xla)(*args)
    named = slots[live]
    require(bool(jnp.array_equal(y[live], y_r[live]))
            and bool(jnp.array_equal(new[named], new_r[named]))
            and not bool(jnp.array_equal(new[named], pool[named])),
            f"conv step, {cell}: y of the {int(live.sum())} live rows and "
            "their slots are the XLA form's, bit for bit")
    rest = np.setdiff1d(np.arange(pool_shape[0]), named)
    require(bool(jnp.array_equal(new[rest], pool[rest]))
            and not bool(jnp.any(y[~live])),
            f"conv step, {cell}: the {len(rest)} slots no live row names "
            "are bit-identical, a dead row reads 0")


def leg_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.recurrent import LSTM
    from deeplearning4j_tpu.ops import kernels
    from deeplearning4j_tpu.ops.attention import (dot_product_attention,
                                                  flash_attention)
    from deeplearning4j_tpu.ops.kernels import conv as kconv
    from deeplearning4j_tpu.ops.kernels import lstm as klstm
    from deeplearning4j_tpu.ops.nn import conv2d

    say("leg kernels: Mosaic-compiled Pallas kernels against their exact "
        "paths, bf16")
    rng = np.random.default_rng(2)

    def arr(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.bfloat16)

    # flash attention at the r05 shape
    B, H, S, D = 4, 12, 2048, 64
    q, k, v = arr(B, H, S, D), arr(B, H, S, D), arr(B, H, S, D)
    _kernel_case(
        f"flash causal B={B} H={H} S={S} D={D}",
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        lambda q, k, v: dot_product_attention(q, k, v, causal=True),
        q, k, v)
    valid = np.array([2048, 1536, 1000, 517])          # ragged right padding
    mask = jnp.asarray(np.arange(S)[None, :] < valid[:, None], jnp.float32)
    _kernel_case(
        f"flash causal + (B, Sk) padding mask B={B}",
        lambda q, k, v: flash_attention(q, k, v, causal=True, mask=mask),
        lambda q, k, v: dot_product_attention(
            q, k, v, mask=mask[:, None, None, :], causal=True),
        q, k, v)

    # the fused LSTM cell inside the recurrent layer's lax.scan
    Bl, T, n_in, Hl = 128, 16, 96, 512
    layer = LSTM(n_in=n_in, n_out=Hl)
    params, _ = layer.initialize(jax.random.PRNGKey(3), (Bl, T, n_in))
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), params)
    x = arr(Bl, T, n_in)
    carry = layer.init_carry(Bl, jnp.bfloat16)

    def lstm_under(impl):
        def run(W, U, b, x):
            with kernels.impl_scope(impl):
                out, _ = layer.apply_seq({"W": W, "U": U, "b": b}, x, carry)
            return out
        return run

    _kernel_case(f"fused LSTM cell in LSTM.apply_seq B={Bl} H={Hl} T={T}",
                 lstm_under("pallas"), lstm_under("exact"),
                 params["W"], params["U"], params["b"], x)
    # the batch-tiled cell: reachable only through a tuning-database winner
    xp, u = arr(8, Bl, 4 * Hl, scale=0.3), arr(Hl, 4 * Hl, scale=0.05)
    h0 = jnp.zeros((Bl, Hl), jnp.bfloat16)
    _kernel_case(
        f"fused LSTM cell b_tile=32 B={Bl} H={Hl}",
        lambda xp, u: klstm.lstm_sequence_fused(
            xp, h0, h0, u, klstm.ORDER_IFOG, "pallas", 32)[0],
        lambda xp, u: klstm.lstm_sequence_exact(xp, h0, h0, u), xp, u)

    # conv2d through the ops/nn.py seam, ResNet-50 res2 geometries
    def conv_under(impl, **kw):
        def run(x, w):
            with kernels.impl_scope(impl):
                return conv2d(x, w, **kw)
        return run

    for tag, xs, ws in (("3x3", (8, 56, 56, 64), (3, 3, 64, 64)),
                        ("1x1", (8, 56, 56, 64), (1, 1, 64, 256))):
        _kernel_case(f"conv2d {tag} stride 1 {xs} -> {ws[-1]}",
                     conv_under("pallas"), conv_under("exact"),
                     arr(*xs), arr(*ws, scale=0.05))
    pads = kconv.resolve_padding("SAME", (56, 56), (3, 3), (1, 1), (1, 1))
    _kernel_case(
        "conv2d 3x3 stride 1 row_tile=8 (tuning-database path)",
        lambda x, w: kconv.conv2d_pallas(x, w, (1, 1), pads, (1, 1), 1,
                                         False, 8),
        conv_under("exact"), arr(8, 56, 56, 64), arr(3, 3, 64, 64, scale=0.05))
    _kda_prefill_case()
    _kda_decode_case()
    _ssm_case()
    # stride 2 is a geometry supports() admits and Mosaic (JAX 0.9.0) refuses
    # to lower: forced pallas must say so, never run another path instead
    x2, w2 = arr(8, 56, 56, 256), arr(1, 1, 256, 128, scale=0.05)
    try:
        _, mosaic = _compile_on_chip(conv_under("pallas", strides=(2, 2)),
                                     x2, w2)
    except Exception as e:  # the compiler's own error is the expected result
        msg = " ".join(str(e).split())
        msg = msg[max(msg.find("Error details:"), 0):]
        say(f"  ok  conv2d stride 2 under forced pallas raised "
            f"{type(e).__name__}: {msg[:150]}")
    else:
        require(mosaic, "conv2d stride 2 under forced pallas compiled with "
                        "tpu_custom_call (no silent exact path)")


# ------------------------------------------------------------ four chips
def leg_four_chips(n: int = 4, batch: int = 1024, image: int = 224,
                   classes: int = 1000, steps: int = 4) -> None:
    import jax

    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.nn.listeners import CollectScoresListener
    from deeplearning4j_tpu.parallel import ParallelWrapper

    say(f"leg four chips: ParallelWrapper(ResNet50 {image}x{image} bf16).fit "
        f"on {n} of {len(jax.devices())} devices, global batch {batch}")
    net = _resnet(image, classes)
    x, y = _resnet_batch(batch, image, classes)
    one_chip = _train_mode_loss(net, x, y)   # whole batch, forward, 1 device
    scores = CollectScoresListener()
    net.set_listeners(scores)
    pw = ParallelWrapper(net, workers=n)
    it = ArrayDataSetIterator(x, y, batch=batch)
    t0 = time.perf_counter()
    pw.fit(it, epochs=steps)
    jax.block_until_ready(net.params)
    say(f"  {steps} steps incl. compile {time.perf_counter() - t0:.1f} s "
        "(info)")
    losses = [s for _, s in scores.scores]
    say(f"  losses {' '.join(f'{v:.4f}' for v in losses)}")
    require(all(np.isfinite(losses)) and losses[-1] < losses[0],
            f"loss finite and falling: {losses[0]:.4f} -> {losses[-1]:.4f}")
    require(abs(losses[0] - one_chip) <= BF16_REL_TOL * one_chip,
            f"first-step loss {losses[0]:.4f} == one-chip loss "
            f"{one_chip:.4f} for the same global batch")

    def devices_of(a):
        return {s.device for s in a.addressable_shards}

    def shard_rows(a):
        return {s.data.shape[0] for s in a.addressable_shards}

    xs, _ys, _w = pw.mesh.pad_shard_batch(x, y)
    require(len(devices_of(xs)) == n and shard_rows(xs) == {batch // n},
            f"batch split {batch // n} rows each over {n} distinct devices")
    params = jax.tree_util.tree_leaves(net.params)
    require(all(len(devices_of(p)) == n
                and all(s.data.shape == p.shape
                        for s in p.addressable_shards) for p in params),
            f"all {len(params)} parameter leaves replicated on {n} devices")
    opt = [a for a in jax.tree_util.tree_leaves(net.opt_states)
           if getattr(a, "ndim", 0) >= 1]
    sharded = [a for a in opt
               if any(s.data.shape != a.shape for s in a.addressable_shards)]
    require(sharded and all(len(devices_of(a)) == n for a in sharded),
            f"{len(sharded)} of {len(opt)} optimizer-state leaves "
            f"ZeRO-sharded over {n} distinct devices")


def main() -> int:
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"jax {jax.__version__} platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']}")
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; no leg run", file=sys.stderr)
        return 2

    from deeplearning4j_tpu.util import get_watcher
    from deeplearning4j_tpu.util.compile_cache import enable_persistent_cache

    cache = enable_persistent_cache()
    watcher = get_watcher()          # hooks in before the first compile
    say(f"compile cache at {cache}")
    t0 = time.perf_counter()
    legs = [leg_trainer, leg_server, leg_kernels]
    if device["count"] >= 4:
        legs.append(leg_four_chips)
    for leg in legs:
        t_leg = time.perf_counter()
        leg()
        gc.collect()                 # drop the leg's device buffers
        say(f"{leg.__name__} passed in {time.perf_counter() - t_leg:.1f} s")
    c = watcher.counts()
    say(f"compiles: backend_compiles={c['backend_compiles']} "
        f"persistent_cache_hits={c['persistent_cache_hits']} "
        f"uncached_compiles={c['uncached_compiles']} "
        f"backend_compile_seconds={c['backend_compile_seconds']:.1f} "
        f"total_wall_s={time.perf_counter() - t0:.1f} (info)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
