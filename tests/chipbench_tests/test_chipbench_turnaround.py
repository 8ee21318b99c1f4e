"""The readers of the host's work between serving batches (PR 39):
``sched_turnaround_ms.open`` / ``.closed`` and ``decode_drain_ms.open``
on made-up counters, nothing from a program without the counters, and
their entries in the manifest."""

import pytest

from chipbench import manifest

OPEN = ["bertL-chat-open", "kimiL-chat-open", "glm47f-chat-open"]
NEW = {"sched_turnaround_ms.open": ("scheduler", "serve_latency_p90_s",
                                    OPEN),
       "sched_turnaround_ms.closed": ("scheduler", "serve_tokens_per_s",
                                      ["bertL-doc-closed"]),
       "decode_drain_ms.open": ("decode engine", "serve_latency_p90_s",
                                OPEN)}
COUNTERS = {"dl4j_serving_batch_turnaround_seconds_total": 0.35,
            "dl4j_serving_batch_turnarounds_total": 5.0,
            "dl4j_serving_generate_drain_seconds_total": 0.24,
            "dl4j_serving_batches_total": 6.0}


def _read(name, counters):
    return manifest.module_from("metrics", name).read({"counters": counters})


@pytest.mark.parametrize("name,want", [
    ("sched_turnaround_ms.open", 70.0),
    ("sched_turnaround_ms.closed", 70.0),
    ("decode_drain_ms.open", 40.0)])
def test_the_readers_on_made_up_counters(name, want):
    assert _read(name, COUNTERS) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("drop", sorted(COUNTERS))
def test_a_missing_counter_or_a_zero_gives_nothing(name, drop):
    missing = {k: v for k, v in COUNTERS.items() if k != drop}
    zero = dict(COUNTERS, **{drop: 0.0})
    reads = (_read(name, missing), _read(name, zero))
    wanted = {"sched_turnaround_ms.open": ("turnaround",),
              "sched_turnaround_ms.closed": ("turnaround",),
              "decode_drain_ms.open": ("drain", "batches_total")}[name]
    if any(w in drop for w in wanted):
        assert reads == (None, None)
    else:
        assert None not in reads
    assert _read(name, {}) is None
    assert manifest.module_from("metrics", name).read({}) is None


def test_the_entries_are_appended_with_the_issues_cells():
    man = manifest.load_manifest()
    tail = man["per_layer"][-3:]
    assert [m["name"] for m in tail] == list(NEW)
    for m in tail:
        layer, moves, cells = NEW[m["name"]]
        assert (m["layer"], m["moves"], m["workloads"]) == \
            (layer, moves, cells)
        assert (m["unit"], m["better"], m["source"]) == \
            ("ms", "lower", "program_counter")
    for name, (_layer, moves, cells) in NEW.items():
        for cell in cells:
            c = manifest.Cell(man, cell)
            assert moves in {e["name"] for e in c.end_to_end()}
            assert name in {m["name"] for m in c.per_layer()}
    assert "sched_turnaround_ms.open" not in {
        m["name"] for m in manifest.Cell(man, "jamba2-chat-open").per_layer()}
