"""DataVec-parity ETL tests — mirrors the reference's CSVRecordReaderTest,
TransformProcessTest and RecordReaderDataSetIteratorTest coverage
(SURVEY.md §2.2 J12, §4)."""

import os

import numpy as np
import pytest

from deeplearning4j_tpu.data import NormalizerStandardize
from deeplearning4j_tpu.datavec import (
    CollectionRecordReader,
    ColumnType,
    CSVRecordReader,
    CSVSequenceRecordReader,
    ImageRecordReader,
    LineRecordReader,
    RecordReaderDataSetIterator,
    RegexLineRecordReader,
    Schema,
    SequenceRecordReaderDataSetIterator,
    SVMLightRecordReader,
    TransformProcess,
    TransformProcessRecordReader,
)


@pytest.fixture
def iris_csv(tmp_path):
    p = tmp_path / "iris.csv"
    rng = np.random.default_rng(0)
    lines = []
    for i in range(30):
        f = rng.uniform(0, 8, 4)
        lines.append(",".join(f"{v:.2f}" for v in f) + f",{i % 3}")
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def test_csv_record_reader(iris_csv):
    rr = CSVRecordReader(iris_csv)
    recs = list(rr)
    assert len(recs) == 30
    assert len(recs[0]) == 5
    assert recs[0][4] == "0"
    # reset semantics
    assert len(list(rr)) == 30


def test_line_and_regex_readers(tmp_path):
    p = tmp_path / "log.txt"
    p.write_text("2026-01-01 INFO start\n2026-01-02 WARN slow\n")
    assert list(LineRecordReader(str(p)))[1] == ["2026-01-02 WARN slow"]
    rr = RegexLineRecordReader(str(p), r"(\S+) (\S+) (\S+)")
    assert list(rr) == [
        ["2026-01-01", "INFO", "start"],
        ["2026-01-02", "WARN", "slow"],
    ]


def test_svmlight_reader(tmp_path):
    p = tmp_path / "data.svm"
    p.write_text("1 1:0.5 3:2.0\n0 2:1.5\n")
    recs = list(SVMLightRecordReader(str(p), num_features=3))
    assert recs[0] == [0.5, 0.0, 2.0, 1.0]
    assert recs[1] == [0.0, 1.5, 0.0, 0.0]


def test_csv_sequence_reader_and_iterator(tmp_path):
    for i, L in enumerate((3, 5)):
        rows = "\n".join(f"{t}.0,{t % 2}" for t in range(L))
        (tmp_path / f"seq_{i}.csv").write_text(rows + "\n")
    rr = CSVSequenceRecordReader(str(tmp_path))
    seqs = list(rr)
    assert [len(s) for s in seqs] == [3, 5]

    it = SequenceRecordReaderDataSetIterator(rr, batch_size=2, label_index=-1, num_classes=2)
    ds = next(iter(it))
    assert ds.features.shape == (2, 5, 1)
    assert ds.labels.shape == (2, 5, 2)
    np.testing.assert_array_equal(ds.features_mask.sum(1), [3, 5])


def test_image_record_reader(tmp_path):
    from PIL import Image

    for label in ("cat", "dog"):
        d = tmp_path / label
        d.mkdir()
        for i in range(2):
            Image.fromarray(
                (np.random.default_rng(i).uniform(0, 255, (20, 16, 3))).astype(np.uint8)
            ).save(d / f"{i}.png")
    rr = ImageRecordReader(height=8, width=10, channels=3, root=str(tmp_path))
    recs = list(rr)
    assert len(recs) == 4
    assert recs[0][0].shape == (8, 10, 3)  # HWC resize
    assert rr.labels == ["cat", "dog"]
    assert {r[1] for r in recs} == {0, 1}

    it = RecordReaderDataSetIterator(rr, batch_size=4, label_index=1, num_classes=2)
    ds = next(iter(it))
    assert ds.features.shape == (4, 8, 10, 3)
    assert ds.labels.shape == (4, 2)


def test_record_reader_dataset_iterator_classification(iris_csv):
    rr = CSVRecordReader(iris_csv)
    it = RecordReaderDataSetIterator(rr, batch_size=8, label_index=4, num_classes=3)
    batches = list(it)
    assert batches[0].features.shape == (8, 4)
    assert batches[0].labels.shape == (8, 3)
    assert sum(b.num_examples() for b in batches) == 30
    np.testing.assert_allclose(batches[0].labels.sum(1), 1.0)
    # with normalizer attached as preprocessor
    norm = NormalizerStandardize().fit(
        RecordReaderDataSetIterator(CSVRecordReader(iris_csv), 30, label_index=4, num_classes=3)
    )
    it2 = RecordReaderDataSetIterator(
        CSVRecordReader(iris_csv), 30, label_index=4, num_classes=3, preprocessor=norm
    )
    ds = next(iter(it2))
    assert abs(float(ds.features.mean())) < 0.05


def test_transform_process_schema_and_records():
    schema = (
        Schema.builder()
        .add_column_string("name")
        .add_column_categorical("color", "red", "green", "blue")
        .add_column_double("size")
        .add_column_integer("count")
        .build()
    )
    tp = (
        TransformProcess.builder(schema)
        .remove_columns("name")
        .categorical_to_one_hot("color")
        .double_math_op("size", "multiply", 2.0)
        .filter(lambda r, s: r[s.column_index("count")] < 0)
        .build()
    )
    fs = tp.final_schema()
    assert fs.column_names() == ["color[red]", "color[green]", "color[blue]", "size", "count"]
    assert fs.column_type("size") == ColumnType.Double

    out = tp.execute([
        ["a", "green", 1.5, 3],
        ["b", "red", 2.0, -1],  # filtered
        ["c", "blue", 0.5, 7],
    ])
    assert out == [[0, 1, 0, 3.0, 3], [0, 0, 1, 1.0, 7]]


def test_transform_conditional_rename_reorder_time():
    schema = (
        Schema.builder()
        .add_column_double("x")
        .add_column_string("ts")
        .build()
    )
    tp = (
        TransformProcess.builder(schema)
        .conditional_replace_value_transform("x", 0.0, lambda v: float(v) < 0)
        .rename_column("x", "clipped")
        .string_to_time("ts", "%Y-%m-%d")
        .reorder_columns("ts")
        .build()
    )
    fs = tp.final_schema()
    assert fs.column_names() == ["ts", "clipped"]
    assert fs.column_type("ts") == ColumnType.Time
    out = tp.execute_record([-3.0, "2026-07-29"])
    assert out[1] == 0.0
    assert isinstance(out[0], int) and out[0] > 1_500_000_000_000


def test_transform_process_record_reader():
    rr = CollectionRecordReader([["1.0", "4"], ["2.0", "5"]])
    schema = Schema.builder().add_column_double("a").add_column_integer("b").build()
    tp = (
        TransformProcess.builder(schema)
        .convert_to_double("a")
        .double_math_op("a", "add", 10.0)
        .build()
    )
    out = list(TransformProcessRecordReader(rr, tp))
    assert out == [[11.0, "4"], [12.0, "5"]]


def test_sequence_iterator_align_end(tmp_path):
    for i, L in enumerate((3, 5)):
        rows = "\n".join(f"{t}.0,{t % 2}" for t in range(L))
        (tmp_path / f"seq_{i}.csv").write_text(rows + "\n")
    rr = CSVSequenceRecordReader(str(tmp_path))
    it = SequenceRecordReaderDataSetIterator(
        rr, batch_size=2, label_index=-1, num_classes=2, alignment_mode="align_end")
    ds = next(iter(it))
    # short sequence right-aligned: padding at the start, data at t=2..4
    np.testing.assert_array_equal(ds.features_mask[0], [0, 0, 1, 1, 1])
    np.testing.assert_array_equal(ds.features_mask[1], [1, 1, 1, 1, 1])
    np.testing.assert_array_equal(ds.features[0, :2, 0], [0.0, 0.0])
    np.testing.assert_array_equal(ds.features[0, 2:, 0], [0.0, 1.0, 2.0])


class TestRound2DataVec:
    """Audio reader, Arrow serde, joins (J12 gaps from VERDICT r1)."""

    def test_wav_record_reader(self, tmp_path):
        import wave

        from deeplearning4j_tpu.datavec.records import WavFileRecordReader

        path = str(tmp_path / "tone.wav")
        sr = 8000
        t = np.arange(sr // 4) / sr
        samples = (np.sin(2 * np.pi * 440 * t) * 32000).astype(np.int16)
        with wave.open(path, "wb") as w:
            w.setnchannels(1)
            w.setsampwidth(2)
            w.setframerate(sr)
            w.writeframes(samples.tobytes())
        rec = next(iter(WavFileRecordReader([path])))
        wavef, rate = rec
        assert rate == sr
        assert wavef.shape == (len(samples), 1)
        np.testing.assert_allclose(
            wavef[:, 0], samples.astype(np.float32) / 32768.0, atol=1e-6)

    def test_arrow_roundtrip(self, tmp_path):
        from deeplearning4j_tpu.datavec.records import (
            ArrowRecordReader,
            write_arrow,
        )

        path = str(tmp_path / "t.feather")
        records = [[1, "a", 0.5], [2, "b", 1.5], [3, "c", 2.5]]
        write_arrow(path, records, ["id", "name", "x"])
        back = list(ArrowRecordReader(path))
        assert back == records

    def test_join_inner_and_outer(self):
        from deeplearning4j_tpu.datavec.transform import Join, Schema

        left = (Schema.Builder().add_column_integer("id")
                .add_column_string("name").build())
        right = (Schema.Builder().add_column_integer("id")
                 .add_column_string("city").build())
        L = [[1, "ann"], [2, "bob"], [3, "cyd"]]
        R = [[1, "oslo"], [1, "pune"], [4, "rome"]]
        inner = (Join.Builder("inner").set_join_columns("id")
                 .set_schemas(left, right).build())
        rows = inner.execute(L, R)
        assert rows == [[1, "ann", "oslo"], [1, "ann", "pune"]]
        assert inner.output_schema().column_names() == ["id", "name", "city"]
        louter = Join("LeftOuter", ["id"], left, right)
        rows = louter.execute(L, R)
        assert [1, "ann", "oslo"] in rows and [2, "bob", None] in rows
        fouter = Join("FullOuter", ["id"], left, right)
        rows = fouter.execute(L, R)
        assert [4, None, "rome"] in rows
        assert len(rows) == 5


class TestTransformBreadth:
    """Round-3 TransformProcess column-op breadth (round-2 deferred item):
    the DataVec transform families beyond the original core set."""

    def _schema(self):
        return (Schema.builder()
                .add_column_string("name")
                .add_column_integer("age")
                .add_column_double("score")
                .add_column_time("ts")
                .build())

    def test_fill_filter_const_dup(self):
        tp = (TransformProcess.builder(self._schema())
              .replace_missing_value_with("age", 0)
              .filter_invalid_values("score")
              .add_constant_column("source", ColumnType.String, "web")
              .duplicate_column("age", "age_copy")
              .build())
        recs = [["a", None, 1.5, 0], ["b", 3, None, 0], ["c", 7, 2.0, 0]]
        out = tp.execute(recs)
        assert out == [["a", 0, 1.5, 0, "web", 0],
                       ["c", 7, 2.0, 0, "web", 7]]
        assert tp.final_schema().column_names() == [
            "name", "age", "score", "ts", "source", "age_copy"]

    def test_int_math_and_categorical_roundtrip(self):
        tp = (TransformProcess.builder(self._schema())
              .integer_math_op("age", "Multiply", 2)
              .integer_math_op("age", "ScalarMin", 10)
              .integer_to_categorical("age", [str(i) for i in range(11)])
              .build())
        out = tp.execute([["a", 3, 0.0, 0], ["b", 9, 0.0, 0]])
        assert [r[1] for r in out] == ["6", "10"]
        assert tp.final_schema().column_type("age") == ColumnType.Categorical

    def test_string_transforms(self):
        tp = (TransformProcess.builder(self._schema())
              .change_case_string_transform("name", upper=True)
              .replace_string_transform("name", "OB", "o")
              .map_string("name", lambda v: v + "!")
              .build())
        out = tp.execute([["bob", 1, 0.0, 0]])
        assert out[0][0] == "Bo!"

    def test_normalize_and_standardize(self):
        tp = (TransformProcess.builder(self._schema())
              .normalize("score", 0.0, 10.0)
              .build())
        assert tp.execute([["a", 1, 5.0, 0]])[0][2] == 0.5
        tp2 = (TransformProcess.builder(self._schema())
               .standardize("score", mean=2.0, stdev=2.0)
               .build())
        assert tp2.execute([["a", 1, 6.0, 0]])[0][2] == 2.0

    def test_derive_time_fields(self):
        # 2021-06-15 13:45:00 UTC
        ms = 1623764700000
        tp = (TransformProcess.builder(self._schema())
              .derive_column_from_time("ts", "hour_of_day")
              .derive_column_from_time("ts", "day_of_week")
              .build())
        out = tp.execute([["a", 1, 0.0, ms]])[0]
        assert out[-2] == 13
        assert out[-1] == 2  # Tuesday (Joda/DataVec: Monday=1..Sunday=7)
        names = tp.final_schema().column_names()
        assert names[-2:] == ["ts_hour_of_day", "ts_day_of_week"]


class TestReducer:
    def test_group_by_aggregations(self):
        from deeplearning4j_tpu.datavec import Reducer

        schema = (Schema.builder()
                  .add_column_string("city")
                  .add_column_double("temp")
                  .add_column_integer("count")
                  .build())
        red = (Reducer.Builder(schema, "city")
               .mean_columns("temp")
               .sum_columns("count")
               .build())
        out = red.execute([
            ["nyc", 10.0, 1], ["sf", 20.0, 2],
            ["nyc", 30.0, 3], ["sf", 10.0, 4],
        ])
        assert out == [["nyc", 20.0, 4.0], ["sf", 15.0, 6.0]]
        names = red.output_schema().column_names()
        assert names == ["city", "mean(temp)", "sum(count)"]

    def test_default_and_stdev(self):
        from deeplearning4j_tpu.datavec import Reducer

        schema = (Schema.builder()
                  .add_column_string("k")
                  .add_column_double("v")
                  .build())
        red = Reducer(schema, ["k"], default_op="stdev")
        out = red.execute([["a", 1.0], ["a", 3.0]])
        np.testing.assert_allclose(out[0][1], np.std([1.0, 3.0], ddof=1))


def _int_schema():
    return (Schema.builder().add_column_string("name")
            .add_column_integer("age").build())


def test_int_math_java_semantics():
    """Divide truncates toward zero, Modulus keeps the dividend's sign
    (Java semantics — review fix)."""
    tp = (TransformProcess.builder(_int_schema())
          .integer_math_op("age", "Divide", 2).build())
    assert tp.execute([["a", -7]])[0][1] == -3
    tp2 = (TransformProcess.builder(_int_schema())
           .integer_math_op("age", "Modulus", 3).build())
    assert tp2.execute([["a", -7]])[0][1] == -1


def test_int_to_categorical_range_checked():
    tp = (TransformProcess.builder(_int_schema())
          .integer_to_categorical("age", ["a", "b"]).build())
    with pytest.raises(ValueError, match="out of range"):
        tp.execute([["x", -1]])


def test_int_math_exact_above_2_53():
    """No float64 detour: Long-range values divide exactly (review fix)."""
    big = 2**53 + 1
    tp = (TransformProcess.builder(_int_schema())
          .integer_math_op("age", "Divide", 1).build())
    assert tp.execute([["a", big]])[0][1] == big


def test_fillna_covers_nan():
    schema = (Schema.builder().add_column_string("n")
              .add_column_double("v").build())
    tp = (TransformProcess.builder(schema)
          .replace_missing_value_with("v", 0.0).build())
    assert tp.execute([["a", float("nan")]])[0][1] == 0.0


def test_etl_worker_code_imports_no_jax():
    """MultiProcessTransformExecutor forks its workers from the training
    process. On a TPU host that parent holds the chip, and a child that
    initialised a JAX backend would fail or hang — so the record-function
    modules the children run must not import JAX at all."""
    import ast

    from deeplearning4j_tpu.datavec import records, transform

    for mod in (records, transform):
        with open(mod.__file__) as f:
            tree = ast.parse(f.read())
        imported = {a.name.split(".")[0] for n in ast.walk(tree)
                    if isinstance(n, ast.Import) for a in n.names}
        imported |= {(n.module or "").split(".")[0] for n in ast.walk(tree)
                     if isinstance(n, ast.ImportFrom)}
        assert "jax" not in imported, mod.__name__
