"""The two delta-rule kernels of ops/kda.py at the Gated DeltaNet's
published head widths (keys 96, values 192, two heads a 384-lane state
row), under the interpreter on the CPU against the token-at-a-time
recurrence, and which kernels a decode step of such heads takes on a TPU.

Everything runs in float32 at ``highest``, so the tolerances are those of
float32 sums taken in another order: 2e-5 on a kernel's outputs and states
of size 4.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.decoder import HybridDecoderBlock
from deeplearning4j_tpu.ops import kda


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _gdn_inputs(key, b, t, h, dk=96, dv=192, decay=1):
    """Unit keys, a decay of one value a head (``decay`` 1; ``dk``: one a
    channel), beta in (1.6, 2): every eigenvalue of a step's transition is
    negative or near 0."""
    ks = jax.random.split(key, 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jnp.exp(jax.random.normal(ks[3], (b, t, h, decay)) * 0.5 - 3.0)
    beta = 1.6 + 0.4 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, dk, dv))


def test_the_state_slot_packs_heads_onto_whole_lane_tiles():
    assert [kda.state_pack(30, dv) for dv in (192, 128, 256, 64)] == \
        [2, 1, 1, 2]
    assert kda.state_pack(3, 192) == 1           # heads that do not pair
    s = jax.random.normal(jax.random.PRNGKey(0), (5, 30, 96, 192))
    packed = kda.pack_states(s, 2)
    assert packed.shape == (5, 15, 96, 384)
    np.testing.assert_array_equal(packed[:, 7, :, 192:], s[:, 15])
    np.testing.assert_array_equal(kda.unpack_states(packed, 2), s)


def test_the_kernels_take_96_192_heads_on_a_tpu_only(monkeypatch):
    assert not kda.on_kernels(30, 96, 192)       # this CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kda.on_kernels(30, 96, 192) and kda.on_kernels(32, 128, 128)
    # narrow values, keys off the sublane tiles, a tail off the lane tiles
    assert not any(kda.on_kernels(*s) for s in
                   ((2, 128, 64), (2, 16, 8), (30, 100, 192), (1, 96, 192)))


@pytest.mark.parametrize("decay", [1, 96], ids=["a-head", "a-channel"])
def test_the_prefill_kernel_at_96_192_is_the_recurrence(decay):
    """Rows of length 0, 7 and 70 (two chunks, the second ragged) in one
    call: each row's final state is the recurrence's at its length, the row
    of length 0 gets back the state it gave, o is the recurrence's up to the
    end of the chunk a length ends in and 0 behind it. A decay a channel is
    read heads first like q and k."""
    q, k, v, g, beta, s0 = _gdn_inputs(jax.random.PRNGKey(0), 3, 70, 2,
                                       decay=decay)
    lens = [0, 7, 70]
    o, s = kda._kda_chunked_pallas(q, k, v, g, beta, s0, jnp.asarray(lens),
                                   interpret=True)
    assert bool(jnp.isfinite(o).all())
    live = (jnp.arange(70)[None] < jnp.asarray(lens)[:, None]) * 1.0
    want_o, want_s = kda.kda_recurrent(q, k, v, g * live[..., None, None],
                                       beta * live[..., None], s0)
    np.testing.assert_allclose(s, want_s, atol=2e-5)
    np.testing.assert_array_equal(s[0], s0[0])
    for i, n in enumerate(lens):
        held = kda.live_chunks(n) * kda.CHUNK
        np.testing.assert_allclose(o[i, :held], want_o[i, :held], atol=2e-5)
        assert not np.asarray(o[i, held:]).any()


def test_the_decode_kernel_at_96_192_steps_the_named_slots_in_place():
    """Four heads, a row's two grid steps a packed pair each; rows live,
    dead (on the trash slot 0) and past their limit (a slot of their own,
    not live): a live row's o and
    slot are ``kda_step``'s to 1e-5 of the largest value, every other slot
    is bit-identical, a dead row's o is 0."""
    h, n_slots = 4, 7
    q, k, v, g, beta, _ = _gdn_inputs(jax.random.PRNGKey(1), 5, 1, h)
    pool = jax.random.normal(jax.random.PRNGKey(2), (n_slots, h // 2, 96,
                                                     384))
    slots = jnp.asarray([3, 0, 6, 1, 2], jnp.int32)
    live = np.asarray([True, False, True, True, False])
    one = lambda a: a[:, 0]
    o, new = kda._kda_decode_pallas(
        one(q), one(k), one(v), one(g), one(beta), pool,
        jnp.where(live, slots, 0), interpret=True, head_group=2)
    want_o, want_s = kda.kda_step(one(q), one(k), one(v), one(g), one(beta),
                                  kda.unpack_states(pool[slots], 2))
    want_s = kda.pack_states(want_s, 2)
    named = np.asarray(slots)[live]
    tol = lambda a: 1e-5 * float(jnp.abs(a).max())
    np.testing.assert_allclose(o[live], want_o[live], atol=tol(want_o))
    np.testing.assert_allclose(new[named], want_s[live], atol=tol(want_s))
    assert float(jnp.abs(new[named] - pool[named]).max()) > 0.01
    assert not np.asarray(o[~live]).any()
    rest = np.asarray([i for i in range(n_slots) if i not in set(named)])
    np.testing.assert_array_equal(new[rest], pool[rest])
    # the XLA form of the pool entry (gather, step, scatter) agrees
    o2, new2 = kda.kda_step_paged(q, k, v, g, beta, pool, slots,
                                  jnp.asarray(live)[:, None])
    np.testing.assert_allclose(o2[live, 0], o[live], atol=tol(want_o))
    np.testing.assert_allclose(new2, new, atol=tol(want_s))


def test_a_gdn_step_takes_both_kernels_under_one_predicate(monkeypatch):
    """On a TPU, one token a row at 96/192 heads: the tail pool and the
    state pool each take their in-place kernel, so the
    ``gdn_decode_states_*`` series count slots both pools visit. (Which
    entry is taken is asked here; what each kernel computes, above.)"""
    taken = []

    def conv(raw, w, pool, slots):
        taken.append("_conv_step_pallas")
        return jnp.zeros_like(raw), pool

    def step(q, k, v, g, beta, pool, slots):
        taken.append("_kda_decode_pallas")
        return jnp.zeros_like(v), pool

    monkeypatch.setattr(kda, "_conv_step_pallas", conv)
    monkeypatch.setattr(kda, "_kda_decode_pallas", step)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    blk = HybridDecoderBlock(hidden_size=32, mixer="gdn", n_heads=2,
                             head_dim=96, value_dim=192, ffn_size=32,
                             norm="post")
    params, _ = blk.initialize(jax.random.PRNGKey(0), None)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 1, 32))
    step_w = jax.jit(lambda p, x, pool: blk.decode_window_paged(
        p, x, pool, jnp.asarray([1, 0, 3]), jnp.asarray([[4], [0], [2]]), 8,
        limits=jnp.asarray([9, -1, 9])))
    out, pool = step_w(params, x, blk.init_pool(5))
    assert bool(jnp.isfinite(out).all())
    assert sorted(taken) == ["_conv_step_pallas", "_kda_decode_pallas"]
    assert pool["state"].shape == (5, 1, 96, 384)
