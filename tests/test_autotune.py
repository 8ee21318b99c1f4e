"""Autotuning subsystem tests (ISSUE 11, docs/AUTOTUNE.md): search-space
registry, equivalence-gated measurement driver, persistent tuning
database, and trace-time consultation by ``auto`` dispatch + conf-time
defaulting.

The satellite contract (mirrored from the checkpoint suite's corruption
discipline and the compile-cache suite's warm-read discipline):

- warm-read: a SECOND database reader (fresh instance over the same
  directory — what a new process sees) re-measures NOTHING, asserted via
  the ``tuning.measurements_total`` counter;
- corrupt/truncated entries are skipped with a loud warning (mirroring
  ``restore_latest_good``), never believed, never a crash;
- keys invalidate when backend/topology changes;
- gate self-tests: a PLANTED slow candidate loses the sweep, a planted
  wrong-output candidate is rejected by the equivalence check.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import tuning
from deeplearning4j_tpu.ops import kernels as K
from deeplearning4j_tpu.ops.kernels import conv as kconv
from deeplearning4j_tpu.ops.kernels import lstm as klstm
from deeplearning4j_tpu.tuning import database as tdb
from deeplearning4j_tpu.util import telemetry as tm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_CONV = {"x_shape": (2, 8, 8, 4), "w_shape": (3, 3, 4, 8),
             "strides": (1, 1), "padding": "SAME", "dilation": (1, 1),
             "groups": 1, "dtype": "float32"}
TINY_LSTM = {"batch": 6, "hidden": 8, "timesteps": 4, "dtype": "float32"}


def _counter(name):
    tele = tm.get_telemetry()
    return tele.counters.get((name, ()), 0.0)


@pytest.fixture
def db(tmp_path):
    """An armed process-global database in a tmp dir; always disarmed on
    exit so no other test sees tuned dispatch."""
    d = tuning.set_database(str(tmp_path / "tdb"))
    try:
        yield d
    finally:
        tuning.set_database(None)


def _driver(db, **kw):
    kw.setdefault("min_window_s", 0.002)
    return tuning.MeasurementDriver(db, **kw)


# ---------------------------------------------------------------------------
# search-space registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_builtin_spaces_registered(self):
        names = tuning.space_names()
        for want in ("conv2d_tiles", "lstm_tiles", "remat_policy",
                     "xla_flags", "bucket_sets", "compression_hosts"):
            assert want in names
        assert "conv2d_tiles" in tuning.measurable_spaces()
        assert "xla_flags" not in tuning.measurable_spaces()

    def test_unknown_space_raises(self):
        with pytest.raises(ValueError, match="unknown search space"):
            tuning.get_space("warp_speed")

    def test_conv_candidates_typed_and_guarded(self):
        sp = tuning.get_space("conv2d_tiles")
        cands = sp.enumerate(TINY_CONV)
        labels = [c.label for c in cands]
        assert "exact" in labels and "pallas:rt=whole" in labels
        by_label = {c.label: c for c in cands}
        # oh=8 -> divisors 1,2,4 below 8
        assert by_label["pallas:rt=2"].params == {"row_tile": 2}
        # the validated-shape guard: a non-dividing tile is rejected
        bad = tuning.Candidate("pallas:rt=3", impl="pallas",
                               params={"row_tile": 3})
        ok, reason = sp.validate(bad, TINY_CONV)
        assert not ok and "does not divide" in reason

    def test_lstm_candidates_guarded(self):
        sp = tuning.get_space("lstm_tiles")
        cands = sp.enumerate(TINY_LSTM)
        assert any(c.params.get("b_tile") == 3 for c in cands)
        ok, reason = sp.validate(
            tuning.Candidate("pallas:bt=4", impl="pallas",
                             params={"b_tile": 4}), TINY_LSTM)
        assert not ok and "does not divide" in reason

    def test_signature_shared_with_dispatch_site(self):
        """The space's DB signature and the ops/nn.py dispatch site use
        ONE builder — drift here would orphan every committed winner."""
        sp = tuning.get_space("conv2d_tiles")
        assert sp.signature(TINY_CONV) == kconv.shape_signature(
            TINY_CONV["x_shape"], TINY_CONV["w_shape"],
            TINY_CONV["strides"], TINY_CONV["padding"],
            TINY_CONV["dilation"], TINY_CONV["groups"])
        sp2 = tuning.get_space("lstm_tiles")
        assert sp2.signature(TINY_LSTM) == klstm.shape_signature(6, 8)

    def test_register_custom_space(self):
        class Dummy(tuning.SearchSpace):
            name = "dummy_space"
            op = "dummy"
            measurable = False
            requires = "nothing"

            def signature(self, ctx):
                return "conf-default"

            def enumerate(self, ctx):
                return [tuning.Candidate("a", is_default=True)]

        tuning.register_space(Dummy())
        try:
            assert "dummy_space" in tuning.space_names()
            with pytest.raises(RuntimeError, match="declared"):
                _driver(tuning.TuningDatabase("/tmp/unused-db")).sweep(
                    tuning.get_space("dummy_space"), {})
        finally:
            tuning.space._REGISTRY.pop("dummy_space", None)


# ---------------------------------------------------------------------------
# measurement driver: equivalence gate + planted self-tests
# ---------------------------------------------------------------------------


class TestDriverGates:
    def test_planted_slow_candidate_loses(self, db):
        """A config handicapped by a per-call sleep must demonstrably
        LOSE the sweep — the gate that proves measurements rank."""
        drv = _driver(db)
        entry = drv.sweep(tuning.get_space("conv2d_tiles"), TINY_CONV,
                          handicap={"exact": 0.05})
        assert entry["status"] == "measured"
        assert entry["winner"]["label"] != "exact"
        rows = {r["label"]: r for r in entry["measured"]}
        assert rows["exact"]["admitted"]            # slow, but correct
        assert rows["exact"]["ms"] > entry["winner"]["ms"]

    def test_planted_wrong_output_rejected(self, db):
        """A candidate whose outputs diverge from the exact path must be
        REJECTED by the equivalence gate — and never timed."""
        drv = _driver(db)
        m0 = _counter("tuning.measurements_total")
        r0 = _counter("tuning.equivalence_rejects_total")
        entry = drv.sweep(
            tuning.get_space("conv2d_tiles"), TINY_CONV,
            corrupt={"pallas:rt=2": lambda o: (o[0] + 1.0,) + tuple(o[1:])})
        rows = {r["label"]: r for r in entry["measured"]}
        assert rows["pallas:rt=2"]["admitted"] is False
        assert "equivalence" in rows["pallas:rt=2"]["reason"]
        assert "ms" not in rows["pallas:rt=2"]      # gate before stopwatch
        assert entry["winner"]["label"] != "pallas:rt=2"
        assert _counter("tuning.equivalence_rejects_total") == r0 + 1
        # only the admitted candidates were measured
        admitted = sum(1 for r in entry["measured"] if r["admitted"])
        assert _counter("tuning.measurements_total") == m0 + admitted

    def test_backend_refusal_is_recorded_not_raised(self, db):
        """The backend's compiler is the VMEM/tiling guard (no budget is
        guessed on the host): a candidate whose program it refuses is
        recorded with the compiler's words, is never timed and cannot win.
        The registered default failing is a bug and propagates."""
        class Refusing(tuning.SearchSpace):
            name, op = "refusing_space", "refusing"

            def signature(self, ctx):
                return "sig"

            def enumerate(self, ctx):
                return [tuning.Candidate("exact", impl="exact",
                                         is_default=True),
                        tuning.Candidate("pallas:big", impl="pallas")]

            def validate(self, cand, ctx):
                return True, ""

            def build(self, ctx):
                def outputs(cand):
                    if cand.label == ctx["refuse"]:
                        raise RuntimeError("Mosaic: scoped vmem exceeded")
                    return (np.zeros(2, np.float32),)
                return tuning.space.MeasureCase(
                    reference=lambda: (np.zeros(2, np.float32),),
                    outputs=outputs, timer=lambda cand: (lambda: None),
                    tolerance=1e-6)

        r0 = _counter("tuning.backend_rejects_total")
        entry = _driver(db).sweep(Refusing(), {"refuse": "pallas:big"})
        rows = {r["label"]: r for r in entry["measured"]}
        assert rows["pallas:big"]["admitted"] is False
        assert "scoped vmem exceeded" in rows["pallas:big"]["reason"]
        assert "ms" not in rows["pallas:big"]
        assert entry["winner"]["label"] == "exact"
        assert _counter("tuning.backend_rejects_total") == r0 + 1
        with pytest.raises(RuntimeError, match="scoped vmem"):
            _driver(db).sweep(Refusing(), {"refuse": "exact"}, force=True)

    def test_all_wrong_refuses_to_commit(self, db):
        """A space whose every candidate fails the gate is a bug, not a
        tuning result: the driver refuses to commit any winner."""
        drv = _driver(db)
        sp = tuning.get_space("lstm_tiles")
        corrupt = {c.label: (lambda o: (o[0] + 1.0,) + tuple(o[1:]))
                   for c in sp.enumerate(TINY_LSTM)}
        with pytest.raises(RuntimeError, match="no candidate passed"):
            drv.sweep(sp, TINY_LSTM, corrupt=corrupt)
        assert db.entries() == 0

    def test_deterministic_random_selection(self, db):
        """Random search with one seed picks the same candidates (the
        deterministic-seeding contract); the default is always included."""
        drv_a = _driver(db, search="random", samples=3, seed=7)
        drv_b = _driver(db, search="random", samples=3, seed=7)
        sp = tuning.get_space("conv2d_tiles")
        sel_a = [c.label for c in drv_a._select(sp, sp.enumerate(TINY_CONV))]
        sel_b = [c.label for c in drv_b._select(sp, sp.enumerate(TINY_CONV))]
        assert sel_a == sel_b
        assert "exact" in sel_a
        sel_c = [c.label for c in _driver(db, search="random", samples=3,
                                          seed=8)
                 ._select(sp, sp.enumerate(TINY_CONV))]
        assert len(sel_c) == len(sel_a)


# ---------------------------------------------------------------------------
# tuning database: persistence contracts
# ---------------------------------------------------------------------------


class TestDatabase:
    def test_warm_read_second_reader_measures_nothing(self, db):
        """The cross-process contract in-process: a FRESH database
        instance over the same directory (what a second process sees) and
        a fresh driver re-measure NOTHING — asserted via the
        tuning.measurements_total counter."""
        drv = _driver(db)
        sp = tuning.get_space("lstm_tiles")
        cold = drv.sweep(sp, TINY_LSTM)
        assert cold["status"] == "measured"
        m0 = _counter("tuning.measurements_total")
        db2 = tuning.TuningDatabase(db.dir)        # fresh reader
        warm = _driver(db2).sweep(sp, TINY_LSTM)
        assert warm["status"] == "warm"
        assert warm["winner"] == cold["winner"]
        assert _counter("tuning.measurements_total") == m0

    def test_independent_cold_sweeps_write_the_same_database(self,
                                                             tmp_path):
        """Two cold sweeps into two fresh directories (what two machines
        of one kind do) write the same key files with the same candidate
        digests and agree on the winner's implementation: a key or a
        digest that held a timing, a path or a process id would make every
        reader re-measure."""
        sp = tuning.get_space("lstm_tiles")
        dirs, winners = [], []
        for name in ("a", "b"):
            d = tuning.TuningDatabase(str(tmp_path / name))
            entry = _driver(d, seed=0).sweep(sp, TINY_LSTM,
                                             handicap={"exact": 0.02})
            assert entry["status"] == "measured"
            dirs.append(d.dir)
            winners.append(entry["winner"]["impl"])
        files = [sorted(f for f in os.listdir(d) if f.endswith(".json"))
                 for d in dirs]
        assert files[0] == files[1] and files[0]
        digests = [[json.load(open(os.path.join(d, f)))["candidates_digest"]
                    for f in fs] for d, fs in zip(dirs, files)]
        assert digests[0] == digests[1]
        assert winners == ["pallas", "pallas"]

    def test_changed_candidate_set_remeasures(self, db):
        """A drifted search space must NOT trust a stale winner: the
        candidates digest mismatch forces a re-measure."""
        drv = _driver(db)
        sp = tuning.get_space("lstm_tiles")
        drv.sweep(sp, TINY_LSTM)
        key = sp.key(TINY_LSTM)
        entry = db.lookup(key)
        entry = dict(entry, candidates_digest="stale")
        db.commit(key, entry)
        m0 = _counter("tuning.measurements_total")
        again = _driver(tuning.TuningDatabase(db.dir)).sweep(sp, TINY_LSTM)
        assert again["status"] == "measured"
        assert _counter("tuning.measurements_total") > m0

    def test_corrupt_entry_skipped_with_warning(self, db, caplog):
        """A truncated/garbage entry is skipped with a loud warning and a
        counter (the restore_latest_good convention) — the database
        degrades to 'unmeasured', it never crashes or believes garbage."""
        drv = _driver(db)
        sp = tuning.get_space("lstm_tiles")
        drv.sweep(sp, TINY_LSTM)
        path = db.entry_paths()[0]
        blob = open(path).read()
        open(path, "w").write(blob[: len(blob) // 2])  # truncate mid-JSON
        c0 = _counter("tuning.corrupt_skipped_total")
        db2 = tuning.TuningDatabase(db.dir)
        with caplog.at_level("WARNING"):
            assert db2.lookup(sp.key(TINY_LSTM)) is None
        assert any("corrupt" in r.message for r in caplog.records)
        assert _counter("tuning.corrupt_skipped_total") == c0 + 1
        # all_records skips it too (the stats surface stays up)
        assert db2.all_records() == []

    def test_hand_written_entry_missing_key_skipped(self, db, caplog):
        """A hand-authored entry (the documented xla_flags path) that
        forgot the \"key\" field is corrupt-skipped, not a trace-time
        KeyError — the 'never a crash' contract covers schema holes."""
        sp = tuning.get_space("lstm_tiles")
        key = sp.key(TINY_LSTM)
        path = db._path(key)
        os.makedirs(db.dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"schema": tdb.SCHEMA_VERSION,
                       "winner": {"label": "exact", "impl": "exact",
                                  "params": {}, "ms": 1.0}}, f)
        c0 = _counter("tuning.corrupt_skipped_total")
        with caplog.at_level("WARNING"):
            assert db.lookup(key) is None
        assert _counter("tuning.corrupt_skipped_total") == c0 + 1

    def test_key_invalidates_on_backend_and_topology_change(self, db,
                                                            monkeypatch):
        """Entries are keyed by (backend, topology): a database harvested
        on one topology must MISS on another, never answer for it."""
        drv = _driver(db)
        sp = tuning.get_space("lstm_tiles")
        drv.sweep(sp, TINY_LSTM)
        assert db.lookup(sp.key(TINY_LSTM)) is not None
        monkeypatch.setattr(tdb, "current_topology", lambda: "tpu:16:v5e")
        db.invalidate_cache()
        assert db.lookup(sp.key(TINY_LSTM)) is None
        monkeypatch.setattr(tdb, "current_backend", lambda: "tpu")
        db.invalidate_cache()
        assert db.lookup(sp.key(TINY_LSTM)) is None

    def test_atomic_commit_leaves_no_tmp(self, db):
        drv = _driver(db)
        drv.sweep(tuning.get_space("lstm_tiles"), TINY_LSTM)
        assert not [f for f in os.listdir(db.dir) if f.endswith(".tmp")]

    def test_stats_and_status_surfaces(self, db):
        drv = _driver(db)
        drv.sweep(tuning.get_space("lstm_tiles"), TINY_LSTM)
        st = db.stats()
        assert st["entries"] == 1
        assert st["entries_by_op"] == {"lstm_cell": 1}
        status = tuning.current_status()
        assert status["entries"] == 1
        assert "tuning.measurements_total" in status["counters"]
        gauges = dict(((n, tuple(sorted(l.items()))), v)
                      for n, l, v in tdb.collect_tuning_gauges())
        assert gauges[("tuning.db_enabled", ())] == 1
        assert gauges[("tuning.db_entries", ())] == 1

    def test_disarmed_status_empty(self):
        assert tuning.get_database() is None
        assert tuning.current_status() == {}
        assert tdb.collect_tuning_gauges() == [("tuning.db_enabled", {}, 0)]

    def test_consultation_is_read_only(self, tmp_path, monkeypatch):
        """resolve() through a DL4J_TPU_TUNING_DB that points nowhere
        must neither crash nor create the directory — consultation is a
        pure read (a typo'd env knob or a read-only mount degrades to
        'unmeasured'); only commit() creates the directory."""
        monkeypatch.setattr(tdb, "_db_dir", tdb._UNSET)
        monkeypatch.setattr(tdb, "_db", None)
        missing = str(tmp_path / "not-yet-harvested")
        monkeypatch.setenv("DL4J_TPU_TUNING_DB", missing)
        assert tdb.resolve("conv2d", "nope", "float32") is None
        assert not os.path.exists(missing)
        db = tuning.get_database()
        db.commit(tdb.TuningKey.for_op("conv2d", "nope", "float32"),
                  {"winner": {"label": "exact", "impl": "exact",
                              "params": {}, "ms": 1.0},
                   "candidates_digest": "t", "measured": []})
        assert os.path.isdir(missing)
        assert tdb.resolve("conv2d", "nope", "float32")["label"] == "exact"

    def test_set_database_none_disarms_over_env(self, tmp_path,
                                                monkeypatch):
        """set_database(None) is explicit OFF, not 'defer to env': the
        fixture/bench teardown contract holds even in a shell where
        DL4J_TPU_TUNING_DB is exported."""
        monkeypatch.setattr(tdb, "_db_dir", tdb._UNSET)
        monkeypatch.setattr(tdb, "_db", None)
        monkeypatch.setenv("DL4J_TPU_TUNING_DB", str(tmp_path / "envdb"))
        assert tuning.get_database() is not None
        tuning.set_database(None)
        assert tdb.database_dir() is None
        assert tuning.get_database() is None
        assert tdb.resolve("conv2d", "nope", "float32") is None


# ---------------------------------------------------------------------------
# trace-time consultation: auto dispatch + conf defaulting
# ---------------------------------------------------------------------------


class TestAutoDispatch:
    def test_auto_resolves_winner_through_db(self, db, monkeypatch):
        """kernel_impl=auto consults the database: a committed pallas
        winner (with its tile) engages the kernel on the exact geometry,
        and the output still matches the exact path."""
        monkeypatch.delenv("DL4J_TPU_KERNEL_IMPL", raising=False)
        from deeplearning4j_tpu.ops import nn as nnops

        drv = _driver(db)
        # plant-slow exact so a pallas tile wins and dispatch has a
        # non-default decision to apply
        entry = drv.sweep(tuning.get_space("conv2d_tiles"), TINY_CONV,
                          handicap={"exact": 0.05})
        assert entry["winner"]["impl"] == "pallas"
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=TINY_CONV["x_shape"]), jnp.float32)
        w = jnp.asarray(rng.normal(size=TINY_CONV["w_shape"]) * 0.1,
                        jnp.float32)
        h0 = _counter("tuning.hits_total")
        out = nnops.conv2d(x, w)
        assert _counter("tuning.hits_total") > h0
        with K.impl_scope("exact"):
            exact = nnops.conv2d(x, w)
        assert float(jnp.max(jnp.abs(out - exact))) < 2e-4

    def test_auto_miss_keeps_honest_prior(self, db, monkeypatch):
        """No entry for the geometry -> auto takes the exact path; an
        exact winner entry also resolves exact."""
        monkeypatch.delenv("DL4J_TPU_KERNEL_IMPL", raising=False)
        sig = kconv.shape_signature((1, 4, 4, 2), (3, 3, 2, 2), (1, 1),
                                    "SAME", (1, 1), 1)
        mode, params = K.dispatch(True, op="conv2d", sig=sig,
                                  dtype="float32")
        assert mode is None and params == {}
        db.commit(tdb.TuningKey.for_op("conv2d", sig, "float32"),
                  {"winner": {"label": "exact", "impl": "exact",
                              "params": {}, "ms": 1.0},
                   "candidates_digest": "t", "measured": []})
        mode, params = K.dispatch(True, op="conv2d", sig=sig,
                                  dtype="float32")
        assert mode is None
        # explicit scopes ignore the database entirely
        with K.impl_scope("exact"):
            assert K.dispatch(True, op="conv2d", sig=sig,
                              dtype="float32")[0] is None

    def test_lstm_auto_uses_tuned_b_tile(self, db, monkeypatch):
        """The recurrent-layer dispatch site consults op=lstm_cell and
        threads the winner's b_tile; layer output matches the exact
        path."""
        monkeypatch.delenv("DL4J_TPU_KERNEL_IMPL", raising=False)
        from deeplearning4j_tpu.nn.recurrent import LSTM as LSTMLayer

        b, h, t, n_in = 6, 8, 5, 4
        sig = klstm.shape_signature(b, h)
        db.commit(tdb.TuningKey.for_op("lstm_cell", sig, "float32"),
                  {"winner": {"label": "pallas:bt=2", "impl": "pallas",
                              "params": {"b_tile": 2}, "ms": 1.0},
                   "candidates_digest": "t", "measured": []})
        lyr = LSTMLayer(n_in=n_in, n_out=h)
        params, _ = lyr.initialize(jax.random.PRNGKey(0), (b, t, n_in))
        x = jnp.asarray(np.random.default_rng(1).normal(size=(b, t, n_in)),
                        jnp.float32)
        carry = lyr.init_carry(b)
        h1 = _counter("tuning.hits_total")
        out_tuned, _ = lyr.apply_seq(params, x, carry)
        assert _counter("tuning.hits_total") > h1
        with K.impl_scope("exact"):
            out_exact, _ = lyr.apply_seq(params, x, carry)
        assert float(jnp.max(jnp.abs(out_tuned - out_exact))) < 1e-4

    def test_tuned_tile_engages_and_stale_tile_raises(self, db, monkeypatch):
        """A committed tiled winner engages the kernel with its tile — no
        host-side VMEM guess stands between a measured winner and its call
        site (the backend that measured it compiled it). A stale winner
        naming a tile that cannot divide OH raises; it does not quietly
        take another path."""
        monkeypatch.delenv("DL4J_TPU_KERNEL_IMPL", raising=False)
        from deeplearning4j_tpu.ops import nn as nnops

        x_shape, w_shape = (1, 16, 8, 4), (3, 3, 4, 8)
        sig = kconv.shape_signature(x_shape, w_shape, (1, 1), "SAME",
                                    (1, 1), 1)
        db.commit(tdb.TuningKey.for_op("conv2d", sig, "float32"),
                  {"winner": {"label": "pallas:rt=2", "impl": "pallas",
                              "params": {"row_tile": 2}, "ms": 1.0},
                   "candidates_digest": "t", "measured": []})
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=x_shape), jnp.float32)
        w = jnp.asarray(rng.normal(size=w_shape) * 0.05, jnp.float32)
        h0 = _counter("tuning.hits_total")
        out = nnops.conv2d(x, w)
        assert _counter("tuning.hits_total") > h0
        with K.impl_scope("exact"):
            exact = nnops.conv2d(x, w)
        scale = max(1.0, float(jnp.max(jnp.abs(exact))))
        assert float(jnp.max(jnp.abs(out - exact))) / scale < 1e-4
        db.commit(tdb.TuningKey.for_op("conv2d", sig, "float32"),
                  {"winner": {"label": "pallas:rt=3", "impl": "pallas",
                              "params": {"row_tile": 3}, "ms": 1.0},
                   "candidates_digest": "t", "measured": []})
        with pytest.raises(ValueError, match="row_tile"):
            nnops.conv2d(x, w)


class TestConfDefaulting:
    def test_remat_policy_defaults_from_db(self, db):
        """An unset remat_policy takes the committed conf-default winner
        at builder time; explicit choices and the env knob always win."""
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration

        db.commit(tdb.TuningKey.for_op("remat_policy", "conf-default",
                                       "any"),
                  {"winner": {"label": "policy:save_conv", "impl": "conf",
                              "params": {"remat_policy": "save_conv"},
                              "ms": 1.0},
                   "candidates_digest": "t", "measured": []})
        b = NeuralNetConfiguration.builder()
        assert b._remat_policy == "save_conv"
        # explicit wins over tuned
        b2 = NeuralNetConfiguration.builder().remat_policy("full")
        assert b2._remat_policy == "full"

    def test_env_knob_wins_over_db(self, db, monkeypatch):
        from deeplearning4j_tpu.config import Environment
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration

        db.commit(tdb.TuningKey.for_op("remat_policy", "conf-default",
                                       "any"),
                  {"winner": {"label": "policy:save_conv", "impl": "conf",
                              "params": {"remat_policy": "save_conv"},
                              "ms": 1.0},
                   "candidates_digest": "t", "measured": []})
        monkeypatch.setenv("DL4J_TPU_REMAT_POLICY", "save_dots")
        monkeypatch.setattr(Environment, "_instance", None)
        try:
            b = NeuralNetConfiguration.builder()
            assert b._remat_policy == "save_dots"
        finally:
            monkeypatch.setattr(Environment, "_instance", None)

    def test_stale_unknown_policy_ignored(self, db):
        """A database naming an unregistered policy degrades to the safe
        default — a stale DB must never crash a config build."""
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration

        db.commit(tdb.TuningKey.for_op("remat_policy", "conf-default",
                                       "any"),
                  {"winner": {"label": "policy:gone", "impl": "conf",
                              "params": {"remat_policy": "gone_policy"},
                              "ms": 1.0},
                   "candidates_digest": "t", "measured": []})
        b = NeuralNetConfiguration.builder()
        assert b._remat_policy is None

    def test_no_db_no_change(self, monkeypatch):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration

        monkeypatch.delenv("DL4J_TPU_TUNING_DB", raising=False)
        assert tuning.get_database() is None
        assert NeuralNetConfiguration.builder()._remat_policy is None


# ---------------------------------------------------------------------------
# the one-command sweep, cross-process
# ---------------------------------------------------------------------------


class TestCrossProcess:
    def test_second_process_remeasures_nothing(self, tmp_path):
        """True cross-process warm read through benchmarks/autotune.py:
        the second PROCESS reports measurements_total == 0 and the
        identical winner."""
        db_dir = str(tmp_path / "xproc-db")
        cmd = [sys.executable,
               os.path.join(REPO, "benchmarks", "autotune.py"),
               "--db", db_dir, "--spaces", "lstm_tiles",
               "--min-window", "0.005", "--json"]
        env = dict(os.environ)
        env.pop("DL4J_TPU_TUNING_DB", None)

        def run():
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  env=env, cwd=REPO, timeout=300)
            assert proc.returncode == 0, proc.stderr
            line = [ln for ln in proc.stdout.splitlines()
                    if ln.startswith("{")][-1]
            return json.loads(line)

        cold = run()
        assert cold["counters"].get("tuning.measurements_total", 0) > 0
        warm = run()
        assert warm["counters"].get("tuning.measurements_total", 0) == 0
        assert [s["status"] for s in warm["spaces"]] == ["warm"]
        assert warm["spaces"][0]["winner"] == cold["spaces"][0]["winner"]
