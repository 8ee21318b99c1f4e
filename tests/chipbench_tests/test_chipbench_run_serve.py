"""A whole run of each serving kind at a tiny size on the CPU (the look for
a chip skipped), a traced run, the refusal to run without a TPU, a token
altered where it is produced, and a metric added as a file only."""

import json
import os

import pytest

from chipbench import manifest, run, trace

import chipbench_tiny as tiny

SMALL = os.path.join(os.path.dirname(trace.__file__), "testdata",
                     "small.xplane.pb")


@pytest.fixture
def tree(tmp_path, monkeypatch):
    tiny.quiet_cache(monkeypatch)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / ".out"))
    return tiny.tiny_tree(tmp_path, monkeypatch)


def measure(man, name, planted=None, trace_on=False, seconds=1.5):
    return run.measure(manifest.Cell(man, name), 2 ** 31 + 23, seconds,
                       trace_on, tiny.DEVICE, planted=planted)


@pytest.mark.parametrize("name,metric", [
    ("tiny-doc-closed", "serve_tokens_per_s"),
    ("tiny-chat-open", "serve_latency_p90_s")])
def test_a_sound_run_is_correct_and_reports_its_metrics(tree, name, metric):
    res = measure(tree, name)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {metric, "setup_s"}
    assert res["metrics"][metric]["value"] > 0
    assert res["attempted"] > 5 and res["failed"] == 0
    assert res["compared"]["malformed_answers"] == {"value": 0.0,
                                                    "limit": 0.0}
    json.dumps(res)


def altered_token(model):
    """A token altered where it is produced."""
    gen = model.generator
    inner = gen._sample
    gen._sample = lambda logits, t, key: (inner(logits, t, key) + 1) % 211


@pytest.mark.parametrize("name", ["tiny-doc-closed", "tiny-chat-open"])
def test_an_altered_token_is_not_correct(tree, name):
    res = measure(tree, name, planted=altered_token)
    assert res["correct"] is False
    assert res["compared"]["logit_gap_max"]["value"] > \
        res["compared"]["logit_gap_max"]["limit"]


def test_a_traced_run_reports_the_per_layer_metrics(tree, monkeypatch):
    # a CPU trace holds no TPU plane: the reduction reads the recorded one
    monkeypatch.setattr(trace, "reduce_logdir", lambda d: trace.reduce_trace(
        trace.read_planes(SMALL)))
    res = measure(tree, "tiny-chat-open", trace_on=True)
    m = res["metrics"]
    assert {"gen_lateness_p95_ms", "sched_queue_wait_p50_s.open",
            "sched_batch_occupancy.open", "serve_step_mfu.open",
            "compiles_in_window", "device_idle_share.open"} <= set(m)
    assert "decode_step_device_ms.open" not in m, \
        "a reader that finds nothing to read returns nothing"
    assert 0 < m["sched_batch_occupancy.open"]["value"] <= 100
    assert 0 < m["device_idle_share.open"]["value"] < 100
    assert m["compiles_in_window"]["value"] == 0
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert "setup_s" not in m


def test_a_metric_is_added_as_a_file_and_an_entry(tree, monkeypatch):
    monkeypatch.setattr(trace, "reduce_logdir", lambda d: trace.reduce_trace(
        trace.read_planes(SMALL)))
    with open(os.path.join(manifest.HERE, "metrics",
                           "requests_seen.py"), "w") as f:
        f.write("def read(run):\n    return len(run['requests'])\n")
    tree["per_layer"].append({
        "name": "requests_seen", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "serve_tokens_per_s", "workloads": ["tiny-doc-closed"]})
    res = measure(tree, "tiny-doc-closed", trace_on=True)
    assert res["metrics"]["requests_seen"]["value"] == res["attempted"]


def test_cells_were_added_as_files_and_entries_only(tree):
    here = manifest.HERE
    import chipbench

    shipped = os.path.dirname(chipbench.__file__)
    for sub in ("kinds", "builders", "reference"):
        assert sorted(os.listdir(os.path.join(here, sub))) == sorted(
            f for f in os.listdir(os.path.join(shipped, sub))
            if f != "__pycache__")
    for f in ("run.py", "train.py", "serve.py", "traffic.py", "manifest.py"):
        with open(os.path.join(here, f)) as a, \
                open(os.path.join(shipped, f)) as b:
            assert a.read() == b.read()
    assert {c["name"] for c in tiny.CELLS} <= {
        w["name"] for w in tree["workloads"]}


def test_without_a_tpu_there_is_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.find_devices(1)
    assert e.value.code == 2
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "resnet50-fit-staged", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code == 2
    out = capsys.readouterr()
    assert out.out == "" and "platform=cpu" in out.err


def test_without_the_program_there_is_no_result(monkeypatch, capsys):
    import sys

    monkeypatch.setitem(sys.modules, "deeplearning4j_tpu", None)
    code = run.main(["--workload", "bertL-chat-open", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert code == 3 and capsys.readouterr().out == ""


def test_an_unknown_workload_is_named(capsys):
    with pytest.raises(KeyError, match="no workload 'nope'"):
        run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"])


@pytest.mark.parametrize("extra", [["--set", "rate_per_s=3"],
                                   ["--cfg", "num_hidden_layers=4"]])
def test_a_run_is_of_the_committed_cell_and_takes_no_override(extra, capsys):
    """What a result line reports is the cell as ``BENCHMARK.json`` and its
    files state it: the entry has no argument that changes a mix or a
    configuration (the readings' tool, calibrate, writes its own into every
    row)."""
    with pytest.raises(SystemExit):
        run.main(["--workload", "bertL-chat-open", "--seed", "1",
                  "--seconds", "1", "--trace", "0"] + extra)
    out = capsys.readouterr()
    assert out.out == "" and "unrecognized arguments" in out.err


def test_calibrate_serving_reads_every_seed_on_one_server(tree, capsys,
                                                         monkeypatch):
    """``--seeds`` on a serving cell: one server, each seed's weights made
    once and put in after the last were let go of, one ``program`` row a
    seed under the harness's own verdict. A row is ``correct`` only where
    the served tokens came from that seed's weights, so the second row
    shows that they were put in. (``calibrate.main`` asks for a TPU;
    ``serving`` is what it calls.)"""
    from chipbench import calibrate, serve

    seeds, made, live, nets = [2 ** 31 + 5, 2 ** 31 + 6], [], [], []
    inner, start = calibrate.module_from, serve.start_server

    def counting(subdir, name):
        mod = inner(subdir, name)
        if subdir == "reference":
            make = mod.make_weights

            def make_weights(seed, cfg):
                # what the model still holds while the next set is made
                live.append([p for net in nets for p in net.params
                             if p is not None])
                made.append(seed)
                return make(seed, cfg)
            mod.make_weights = make_weights
        return mod

    def start_server(*a, **kw):
        server, model = start(*a, **kw)
        nets.append(model.net)
        return server, model

    monkeypatch.setattr(calibrate, "module_from", counting)
    monkeypatch.setattr(serve, "start_server", start_server)
    calibrate.serving(manifest.Cell(tree, "tiny-chat-open"), seeds,
                      seeds[1:], 1.5)
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert [(r["who"], r["seed"]) for r in rows] == [
        ("program", seeds[0]), ("program", seeds[1]), ("control", seeds[1])]
    assert all(r["correct"] is True and r["over"] == []
               for r in rows if r["who"] == "program"), rows
    assert rows[0]["tokens_per_s"] > 0 and rows[1]["tokens_per_s"] > 0
    assert made == seeds, "each seed's weights are made once"
    assert live == [[], []], "and none is held while the next are made"
