"""The comparison that decides ``correct`` and the load-generating child,
each on made-up inputs."""

import http.server
import json
import subprocess
import sys
import threading
import time

import pytest

from chipbench import checks, loadgen, manifest, serve

LIMITS = {"loss_gap": 0.01, "grad_norm_gap": 0.1, "change_norm_gap": 0.1,
          "big_leaf_size": 1000, "big_grad_norm_gap": 0.2,
          "grad_angle": {"head": 0.01, "conv": 0.02}}


def numbers(head=(1.0, 2.0, 3.0), conv=(1.0, -1.0), **over):
    want = {"losses": [7.0, 6.9, 6.8],
            "grad_norms": {"a": 1.0, "b": 2.0, "c": 1e-6},
            "change_norms": {"a": 0.1, "b": 0.2, "c": 0.05},
            "first_grads": {"head": [1.0, 2.0, 3.0], "conv": [1.0, -1.0]},
            "sizes": {"a": 64, "b": 5000, "c": 2000}}
    got = {k: (list(v) if isinstance(v, list) else dict(v))
           for k, v in want.items() if k != "sizes"}
    got["first_grads"] = {"head": list(head), "conv": list(conv)}
    for key, (leaf, value) in over.items():
        got[key][leaf] = value
    return checks.training_numbers(got, want, LIMITS)


def test_equal_readings_are_correct():
    ns = numbers()
    assert [n["name"] for n in ns] == ["loss1_gap", "loss2_gap", "loss3_gap",
                                       "grad_norm_gap", "big_grad_norm_gap",
                                       "grad_angle.head",
                                       "grad_angle.conv", "change_norm_gap"]
    assert checks.verdict(ns) and all(abs(n["value"]) < 1e-12 for n in ns)


def test_gap_is_of_norms_against_the_leafs_or_the_median_leafs_norm():
    want = {"a": 1.0, "b": 2.0, "c": 1e-6}
    # leaf c's norm is all but zero: its gap is held against the median
    assert checks.worst_leaf_gap(dict(want, c=0.1), want) == pytest.approx(
        (0.1 - 1e-6) / 1.0)
    assert checks.worst_leaf_gap(dict(want, b=3.0), want) == pytest.approx(0.5)
    assert checks.leaf_gaps(dict(want, b=3.0), want) == [0.0, 0.5, 0.0]


def test_the_gradient_is_judged_by_its_median_leaf():
    # one small leaf far off does not move the median; all leaves off does
    ns = {n["name"]: n["value"] for n in numbers(grad_norms=("a", 1.5))}
    assert ns["grad_norm_gap"] == 0.0
    want = {"a": 1.0, "b": 2.0, "c": 4.0}
    assert checks.median_leaf_gap({k: 1.4 * v for k, v in want.items()},
                                  want) == pytest.approx(0.4)


def test_big_leaves_are_judged_by_the_worst_of_them():
    # a, a vector of 64 numbers, may be off by half: the median holds
    ns = {n["name"]: n["value"] for n in numbers(grad_norms=("a", 1.5))}
    assert ns["grad_norm_gap"] == 0.0 and ns["big_grad_norm_gap"] == 0.0
    # b, 5,000 numbers, may not: one big leaf of the wrong size fails
    bad = numbers(grad_norms=("b", 1.0))
    assert [n["name"] for n in bad if n["value"] > n["limit"]] == [
        "big_grad_norm_gap"]
    assert checks.worst_leaf_gap({"a": 9.0, "b": 2.0}, {"a": 1.0, "b": 2.0},
                                 only={"b"}) == 0.0


def test_the_angle_sees_direction_and_not_length():
    assert checks.angle([1, 2, 3], [2, 4, 6]) == pytest.approx(0.0, abs=1e-12)
    assert checks.angle([1, 0], [0, 1]) == pytest.approx(1.0)
    assert checks.angle([1, 0], [-1, 0]) == pytest.approx(2.0)
    assert not checks.verdict(numbers(head=(3.0, 2.0, 1.0)))
    assert checks.verdict(numbers(head=(2.0, 4.0, 6.0)))


def test_every_leaf_named_under_grad_angle_has_a_limit_of_its_own():
    ns = {n["name"]: n for n in numbers(conv=(1.0, -0.82))}
    assert ns["grad_angle.conv"]["limit"] == 0.02
    assert ns["grad_angle.head"]["limit"] == 0.01
    assert 0.004 < ns["grad_angle.conv"]["value"] < 0.01   # under conv's
    assert checks.verdict(list(ns.values()))
    # one leaf's gradient wrong, every norm and loss as it should be
    bad = numbers(conv=(-1.0, 1.0))
    assert [n["name"] for n in bad if n["value"] > n["limit"]] == [
        "grad_angle.conv"]


def test_a_leaf_the_reference_does_not_move_is_left_out_of_the_change():
    # c's reference gradient is under a thousandth of the median leaf's
    ns = numbers(change_norms=("c", 5.0))
    assert checks.verdict(ns)
    assert not checks.verdict(numbers(change_norms=("a", 0.2)))


@pytest.mark.parametrize("key,leaf,value", [
    ("losses", 0, 7.2), ("change_norms", "b", 0.0),
    ("losses", 2, float("nan"))])
def test_one_number_over_its_limit_is_not_correct(key, leaf, value):
    assert not checks.verdict(numbers(**{key: (leaf, value)}))


def test_serving_numbers():
    ok = checks.serving_numbers([0.0, 1e-5], 0, {"logit_gap_max": 1e-3})
    assert checks.verdict(ok)
    assert not checks.verdict(checks.serving_numbers(
        [0.0, 0.5], 0, {"logit_gap_max": 1e-3}))
    assert not checks.verdict(checks.serving_numbers(
        [0.0], 1, {"logit_gap_max": 1e-3})), "a malformed answer: limit 0"
    assert not checks.verdict(checks.serving_numbers(
        [], 0, {"logit_gap_max": 1e-3})), "nothing compared is not correct"
    assert not checks.verdict([])


def test_prometheus_text_sums_label_sets():
    text = ('# HELP x\nfoo_total{model="a"} 2\nfoo_total{model="b"} 3\n'
            "bar 1.5\nbad line\n")
    assert serve.prometheus(text) == {"foo_total": 5.0, "bar": 1.5}


class _Echo(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        time.sleep(0.01)
        out = json.dumps({"tokens": [[7] * body["max_new_tokens"]]}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)

    def log_message(self, *a):
        pass


@pytest.fixture
def echo():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Echo)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}/v1/models/cell/generate"
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5)
    assert not t.is_alive()


def test_open_loop_sends_when_due_and_times_from_then(echo):
    reqs = [{"due": 0.05 * i, "prompt": [1, 2], "max_new_tokens": 3}
            for i in range(10)]
    out = loadgen.run({"url": echo, "mode": "open", "start": time.time(),
                       "seconds": 1.0, "clients": 4, "grace_s": 5,
                       "requests": reqs})
    rows = out["requests"]
    assert len(rows) == 10 and out["never_sent"] == 0
    assert all(r["status"] == 200 and r["tokens"] == [7, 7, 7] for r in rows)
    assert all(0 <= r["sent"] - r["due"] < 0.05 for r in rows), "lateness"
    assert all(r["done"] - r["due"] >= 0.01 for r in rows)


def test_closed_loop_stops_sending_when_the_window_closes(echo):
    reqs = [{"due": None, "prompt": [1], "max_new_tokens": 1}] * 1000
    out = loadgen.run({"url": echo, "mode": "closed", "start": time.time(),
                       "seconds": 0.3, "clients": 2, "grace_s": 5,
                       "requests": reqs})
    rows = out["requests"]
    assert 4 <= len(rows) < 100
    assert max(r["sent"] for r in rows) < 0.3 + 0.05


def test_a_refused_connection_is_a_failed_request_not_a_crash():
    out = loadgen.run({"url": "http://127.0.0.1:9/x", "mode": "open",
                       "start": time.time(), "seconds": 0.2, "clients": 1,
                       "grace_s": 1, "requests": [
                           {"due": 0.0, "prompt": [1], "max_new_tokens": 1}]})
    assert out["requests"][0]["status"] != 200
    assert out["requests"][0]["tokens"] is None


def test_the_child_never_imports_jax(echo):
    job = {"url": echo, "mode": "open", "start": time.time() + 0.2,
           "seconds": 0.5, "clients": 1, "grace_s": 2, "requests": [
               {"due": 0.0, "prompt": [1], "max_new_tokens": 2}]}
    code = ("import sys, json, chipbench.loadgen as l; "
            "out = l.run(json.load(sys.stdin)); "
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules; "
            "json.dump(out, sys.stdout)")
    p = subprocess.run([sys.executable, "-c", code], cwd=manifest.ROOT,
                       input=json.dumps(job).encode(), capture_output=True,
                       timeout=60)
    assert p.returncode == 0, p.stderr.decode()[-500:]
    assert json.loads(p.stdout)["requests"][0]["tokens"] == [7, 7]


def test_a_reading_made_under_an_override_says_so_in_every_row(capsys,
                                                               monkeypatch):
    from chipbench import calibrate

    ns = numbers()
    calibrate._show("program", 3, ns)
    monkeypatch.setattr(calibrate, "_OVERRIDES", {"matmul_precision": None})
    calibrate._show("program", 3, numbers(head=(3.0, 2.0, 1.0)))
    plain, over = [json.loads(ln) for ln in
                   capsys.readouterr().out.splitlines()]
    assert "overrides" not in plain and plain["correct"] is True
    assert over["overrides"] == {"matmul_precision": None}
    assert over["correct"] is False and over["over"] == ["grad_angle.head"]
