"""Full-architecture import regression corpus (VERDICT r3 missing #1).

Reference parity: the reference regression-tests its TF importer against
hundreds of COMPLETE frozen graphs with recorded goldens
(nd4j-tf-graph-tests, TFGraphTestAllSameDiff-style runner — SURVEY.md §4),
not just per-op blocks. Offline equivalent here:

- TF side: every frozen ``tf.keras.applications`` architecture below is
  built in-test (random init — a random-init graph exercises the import
  rules exactly as well as pretrained bits), frozen with
  ``convert_variables_to_constants_v2``, imported, and matched against
  TF's own forward output at tight fp32 tolerance.
- ONNX side: real published torch architectures — ResNet-18 (He et al.),
  a MobileNetV3-flavoured SE/hardswish block net, torch LSTM/GRU seq
  models, and transformers' BERT / GPT-2 / DistilBERT (random-init
  configs; no torchvision/onnx in the image, so conv nets are standard
  architectures written with torch.nn and everything exports through
  ``torch.onnx.export``).
- Fine-tune: two of the conv nets train one/two steps after import
  (convert_to_variable → fit), proving the imported graphs are not just
  forward-correct but trainable.

Small input resolutions keep single-core CPU runtime sane; goldens run on
CPU (conftest pins the platform) where fp32 matches the source framework.
"""

import io
from typing import List

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")
torch = pytest.importorskip("torch")

from deeplearning4j_tpu.imports import import_graph_def, import_onnx  # noqa: E402


# --------------------------------------------------------------------- TF


def _freeze_keras(model):
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2,
    )

    shp = model.input_shape[1:]
    conc = tf.function(lambda v: model(v, training=False)).get_concrete_function(
        tf.TensorSpec((None,) + shp, tf.float32))
    frozen = convert_variables_to_constants_v2(conc)
    gd = frozen.graph.as_graph_def()
    in_name = frozen.inputs[0].name.split(":")[0]
    out_name = frozen.outputs[0].name
    return gd, frozen, in_name, out_name


RES = 64
_TF_APPS = {
    # name -> builder; include_top=False + pooling exercises every conv/BN/
    # activation block (the head is a plain Dense, covered elsewhere)
    "ResNet50": lambda: tf.keras.applications.ResNet50(
        weights=None, include_top=False, input_shape=(RES, RES, 3), pooling="avg"),
    "ResNet50V2": lambda: tf.keras.applications.ResNet50V2(
        weights=None, include_top=False, input_shape=(RES, RES, 3), pooling="avg"),
    "MobileNetV2": lambda: tf.keras.applications.MobileNetV2(
        weights=None, include_top=False, input_shape=(RES, RES, 3), pooling="avg"),
    "MobileNetV3Small": lambda: tf.keras.applications.MobileNetV3Small(
        weights=None, include_top=False, input_shape=(RES, RES, 3), pooling="avg",
        include_preprocessing=True),
    "EfficientNetB0": lambda: tf.keras.applications.EfficientNetB0(
        weights=None, include_top=False, input_shape=(RES, RES, 3), pooling="avg"),
    "DenseNet121": lambda: tf.keras.applications.DenseNet121(
        weights=None, include_top=False, input_shape=(RES, RES, 3), pooling="avg"),
    "InceptionV3": lambda: tf.keras.applications.InceptionV3(
        weights=None, include_top=False, input_shape=(96, 96, 3), pooling="avg"),
    "VGG16": lambda: tf.keras.applications.VGG16(
        weights=None, include_top=False, input_shape=(RES, RES, 3), pooling="avg"),
    "Xception": lambda: tf.keras.applications.Xception(
        weights=None, include_top=False, input_shape=(96, 96, 3), pooling="avg"),
    "NASNetMobile": lambda: tf.keras.applications.NASNetMobile(
        weights=None, include_top=False, input_shape=(RES, RES, 3), pooling="avg"),
}


class TestTFFullModelCorpus:
    # slow: the two heaviest goldens (NASNetMobile, InceptionV3: about
    # half a minute each in the longest file but one); the other
    # parameters of this same test are the tier-1 cases of the import seam
    @pytest.mark.parametrize(
        "name",
        [pytest.param(n, marks=pytest.mark.slow)
         if n in ("NASNetMobile", "InceptionV3") else n
         for n in sorted(_TF_APPS)])
    def test_forward_golden(self, name, rng):
        tf.keras.utils.set_random_seed(7)
        model = _TF_APPS[name]()
        gd, frozen, in_name, out_name = _freeze_keras(model)
        shp = model.input_shape[1:]
        x = rng.normal(size=(2,) + shp).astype(np.float32)
        golden = frozen(tf.constant(x))
        if isinstance(golden, (list, tuple)):
            golden = golden[0]
        golden = np.asarray(golden)

        sd = import_graph_def(gd)
        key = sd.tf_name_map[out_name]
        res = np.asarray(sd.output({in_name: x}, [key])[key])
        # fp32 CPU both sides; rel tol covers conv reduction-order noise
        np.testing.assert_allclose(res, golden, atol=1e-4, rtol=1e-4)

    # NOTE: MobileNetV2/EfficientNet at random init collapse activations to
    # ~1e-12 through their deep inference-mode BN stacks — gradients vanish
    # below fp32 resolution, which is an init property, not an import
    # property. ResNet50 (residual skips preserve scale) and VGG16 (no BN)
    # are the trainable-at-random-init picks.
    @pytest.mark.parametrize("name", ["ResNet50", "VGG16"])
    def test_finetune_one_step(self, name, rng):
        """Imported frozen graph → convert conv kernels to variables →
        fit: the loss must move and stay finite (trainability proof)."""
        from deeplearning4j_tpu.nn.updaters import Adam
        from deeplearning4j_tpu.samediff import TrainingConfig

        tf.keras.utils.set_random_seed(7)
        builder = {
            "ResNet50": lambda: tf.keras.applications.ResNet50(
                weights=None, include_top=False, input_shape=(32, 32, 3),
                pooling="avg"),
            "VGG16": lambda: tf.keras.applications.VGG16(
                weights=None, include_top=False, input_shape=(32, 32, 3),
                pooling="avg"),
        }[name]
        model = builder()
        gd, frozen, in_name, out_name = _freeze_keras(model)
        sd = import_graph_def(gd)

        kernels = [n for n, v in sd._arrays.items() if np.asarray(v).ndim == 4]
        assert kernels, "no conv kernels found in imported graph"
        sd.convert_to_variable(*kernels)

        C = 2
        feat = sd.get_variable(sd.tf_name_map[out_name])
        width = int(feat.shape[-1])
        w = sd.constant(
            (rng.normal(size=(width, C)) * 0.05).astype(np.float32), "head_w")
        sd.convert_to_variable("head_w")
        logits = sd._op("matmul", [feat, w])
        y = sd.placeholder("y", shape=(-1, C))
        loss = sd.loss.softmaxCrossEntropy(logits, y)
        sd.set_loss_variables(loss)
        sd.set_training_config(TrainingConfig(
            updater=Adam(1e-2),
            data_set_feature_mapping=[in_name],
            data_set_label_mapping=["y"]))

        x = rng.normal(size=(4, 32, 32, 3)).astype(np.float32)
        labels = np.eye(C, dtype=np.float32)[rng.integers(0, C, size=4)]
        k0 = kernels[0]
        before = np.asarray(sd._arrays[k0]).copy()
        hist = sd.fit((x, labels), epochs=2)
        assert np.isfinite(hist).all(), hist
        assert hist[1] != hist[0], "loss did not move"
        assert not np.array_equal(np.asarray(sd._arrays[k0]), before), \
            "converted kernel did not update"


# ------------------------------------------------------------------- ONNX


def _export_onnx(model, x):
    from torch.onnx._internal.torchscript_exporter import onnx_proto_utils

    # the TorchScript exporter builds+serializes the ModelProto itself and
    # only needs the `onnx` package (absent in this image) to splice in
    # onnxscript custom functions, which none of these models use
    orig = onnx_proto_utils._add_onnxscript_fn
    onnx_proto_utils._add_onnxscript_fn = lambda mb, co: mb
    try:
        buf = io.BytesIO()
        torch.onnx.export(model, (x,), buf, input_names=["x"],
                          output_names=["y"], dynamo=False)
        return buf.getvalue()
    finally:
        onnx_proto_utils._add_onnxscript_fn = orig


class _BasicBlock(torch.nn.Module):
    """ResNet BasicBlock (He et al. 2015)."""

    def __init__(self, cin, cout, stride=1):
        super().__init__()
        nn = torch.nn
        self.conv1 = nn.Conv2d(cin, cout, 3, stride, 1, bias=False)
        self.bn1 = nn.BatchNorm2d(cout)
        self.conv2 = nn.Conv2d(cout, cout, 3, 1, 1, bias=False)
        self.bn2 = nn.BatchNorm2d(cout)
        self.down = None
        if stride != 1 or cin != cout:
            self.down = nn.Sequential(
                nn.Conv2d(cin, cout, 1, stride, bias=False),
                nn.BatchNorm2d(cout))
        self.relu = nn.ReLU()

    def forward(self, x):
        idn = x if self.down is None else self.down(x)
        h = self.relu(self.bn1(self.conv1(x)))
        h = self.bn2(self.conv2(h))
        return self.relu(h + idn)


class _ResNet18(torch.nn.Module):
    def __init__(self, classes=10):
        super().__init__()
        nn = torch.nn
        self.stem = nn.Sequential(
            nn.Conv2d(3, 64, 7, 2, 3, bias=False), nn.BatchNorm2d(64),
            nn.ReLU(), nn.MaxPool2d(3, 2, 1))
        blocks, cin = [], 64
        for cout, stride in [(64, 1), (64, 1), (128, 2), (128, 1),
                             (256, 2), (256, 1), (512, 2), (512, 1)]:
            blocks.append(_BasicBlock(cin, cout, stride))
            cin = cout
        self.blocks = nn.Sequential(*blocks)
        self.pool = nn.AdaptiveAvgPool2d(1)
        self.fc = nn.Linear(512, classes)

    def forward(self, x):
        return self.fc(self.pool(self.blocks(self.stem(x))).flatten(1))


class _MobileSE(torch.nn.Module):
    """MobileNetV3-flavoured: depthwise separable + SE + hardswish."""

    def __init__(self):
        super().__init__()
        nn = torch.nn
        self.stem = nn.Sequential(nn.Conv2d(3, 16, 3, 2, 1, bias=False),
                                  nn.BatchNorm2d(16), nn.Hardswish())
        self.dw = nn.Sequential(
            nn.Conv2d(16, 16, 3, 1, 1, groups=16, bias=False),
            nn.BatchNorm2d(16), nn.ReLU())
        self.se_pool = nn.AdaptiveAvgPool2d(1)
        self.se_fc1 = nn.Conv2d(16, 8, 1)
        self.se_fc2 = nn.Conv2d(8, 16, 1)
        self.pw = nn.Sequential(nn.Conv2d(16, 32, 1, bias=False),
                                nn.BatchNorm2d(32), nn.Hardswish())
        self.head = nn.Linear(32, 7)

    def forward(self, x):
        h = self.dw(self.stem(x))
        s = torch.sigmoid(
            self.se_fc2(torch.relu(self.se_fc1(self.se_pool(h)))))
        h = self.pw(h * s)
        return self.head(h.mean(dim=(2, 3)))


class _LSTMSeq(torch.nn.Module):
    def __init__(self):
        super().__init__()
        nn = torch.nn
        self.emb = nn.Embedding(50, 16)
        self.lstm = nn.LSTM(16, 32, num_layers=2, batch_first=True)
        self.head = nn.Linear(32, 5)

    def forward(self, tok):
        h, _ = self.lstm(self.emb(tok))
        return self.head(h[:, -1])


class _GRUSeq(torch.nn.Module):
    def __init__(self):
        super().__init__()
        nn = torch.nn
        self.emb = nn.Embedding(50, 16)
        self.gru = nn.GRU(16, 32, batch_first=True, bidirectional=True)
        self.head = nn.Linear(64, 5)

    def forward(self, tok):
        h, _ = self.gru(self.emb(tok))
        return self.head(h[:, -1])


def _hf_wrap(model):
    class Wrap(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.m = model

        def forward(self, tok):
            return self.m(input_ids=tok).last_hidden_state

    return Wrap()


def _bert_tiny():
    from transformers import BertConfig, BertModel

    return _hf_wrap(BertModel(BertConfig(
        vocab_size=100, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64)))


def _gpt2_tiny():
    from transformers import GPT2Config, GPT2Model

    return _hf_wrap(GPT2Model(GPT2Config(
        vocab_size=100, n_positions=64, n_embd=32, n_layer=2, n_head=2)))


def _distilbert_tiny():
    from transformers import DistilBertConfig, DistilBertModel

    return _hf_wrap(DistilBertModel(DistilBertConfig(
        vocab_size=100, dim=32, n_layers=2, n_heads=2, hidden_dim=64,
        max_position_embeddings=64)))


_ONNX_MODELS = {
    "resnet18": (_ResNet18, lambda: torch.randn(2, 3, 64, 64)),
    "mobile_se": (_MobileSE, lambda: torch.randn(2, 3, 32, 32)),
    "lstm_seq": (_LSTMSeq, lambda: torch.randint(0, 50, (2, 12))),
    "gru_seq": (_GRUSeq, lambda: torch.randint(0, 50, (2, 12))),
    "bert_tiny": (_bert_tiny, lambda: torch.randint(0, 100, (2, 10))),
    "gpt2_tiny": (_gpt2_tiny, lambda: torch.randint(0, 100, (2, 10))),
    "distilbert_tiny": (_distilbert_tiny, lambda: torch.randint(0, 100, (2, 10))),
}


class TestONNXFullModelCorpus:
    @pytest.mark.parametrize("name", sorted(_ONNX_MODELS))
    def test_forward_golden(self, name):
        torch.manual_seed(0)
        mk, mkx = _ONNX_MODELS[name]
        model = mk().eval()
        x = mkx()
        data = _export_onnx(model, x)
        sd = import_onnx(data)
        out = np.asarray(sd.output({"x": x.numpy()}, ["y"])["y"])
        with torch.no_grad():
            golden = model(x).numpy()
        np.testing.assert_allclose(out, golden, atol=1e-4, rtol=1e-4)

    def test_resnet18_save_load_roundtrip(self, tmp_path):
        """Imported full-model graphs must survive serialization."""
        torch.manual_seed(0)
        model = _ResNet18().eval()
        x = torch.randn(1, 3, 64, 64)
        sd = import_onnx(_export_onnx(model, x))
        ref = np.asarray(sd.output({"x": x.numpy()}, ["y"])["y"])
        p = str(tmp_path / "rn18.sdz")
        sd.save(p)
        from deeplearning4j_tpu.samediff import SameDiff

        sd2 = SameDiff.load(p)
        out = np.asarray(sd2.output({"x": x.numpy()}, ["y"])["y"])
        np.testing.assert_allclose(out, ref, atol=1e-6)


# ----------------------------------------------------- ONNX control flow
# VERDICT r3 missing #3: Loop/If/Scan + Einsum. torch scripted control flow
# exports ONNX Loop/If subgraphs; the importer lowers them to ONE
# lax.while_loop / lax.scan / lax.cond custom node each (same collapse as
# the TF side's While/If — reference: samediff-import-onnx, path-cite).


class _ForLoopNet(torch.nn.Module):
    def forward(self, x):
        h = x
        for i in range(5):
            h = h * 0.5 + 1.0
        return h


class _WhileLoopNet(torch.nn.Module):
    def forward(self, x):
        h = x
        while h.sum() < 100.0:
            h = h * 2.0
        return h


class _CondNet(torch.nn.Module):
    def forward(self, x):
        if x.sum() > 0:
            y = x * 2.0
        else:
            y = x - 3.0
        return y


class _EinsumNet(torch.nn.Module):
    def forward(self, a, b):
        return torch.einsum("bij,bjk->bik", a, b)


class _GreedyDecode(torch.nn.Module):
    """toy greedy decoder: embed last token, fused cell, argmax — the
    'torch-exported greedy-decode loop imports and matches' criterion."""

    def __init__(self):
        super().__init__()
        nn = torch.nn
        self.emb = nn.Embedding(20, 16)
        self.cell = nn.Linear(32, 16)
        self.out = nn.Linear(16, 20)

    def forward(self, tok0: torch.Tensor, h0: torch.Tensor) -> torch.Tensor:
        tok = tok0
        h = h0
        outs: List[torch.Tensor] = []
        for i in range(6):
            e = self.emb(tok).squeeze(1)
            h = torch.tanh(self.cell(torch.cat([e, h], dim=1)))
            logits = self.out(h)
            tok = logits.argmax(dim=1, keepdim=True)
            outs.append(tok)
        return torch.cat(outs, dim=1)


class _OpTailNet(torch.nn.Module):
    """exercises the round-4 ONNX rule tail in one traced graph:
    Asin/Atan/Acos, ReduceLogSumExp, Celu, Shrink (torch Softshrink),
    HardSwish (torch's legacy exporter has no aten::sinh family symbolic;
    those _OUN entries map 1:1 onto registry ops with their own coverage)."""

    def forward(self, x):
        xc = torch.clamp(x, -0.9, 0.9)
        a = torch.asin(xc) + torch.atan(x) + torch.acos(xc)
        b = x * 0.1
        c = torch.logsumexp(x, dim=1, keepdim=True)
        d = torch.nn.functional.celu(x, alpha=0.7)
        e = torch.nn.functional.softshrink(x, lambd=0.3)
        f = torch.nn.functional.hardswish(x)
        return a + b + c + d + e + f


def _export_scripted(model, xs):
    from torch.onnx._internal.torchscript_exporter import onnx_proto_utils

    orig = onnx_proto_utils._add_onnxscript_fn
    onnx_proto_utils._add_onnxscript_fn = lambda mb, co: mb
    try:
        buf = io.BytesIO()
        torch.onnx.export(torch.jit.script(model), tuple(xs), buf,
                          input_names=[f"x{i}" for i in range(len(xs))],
                          output_names=["y"], dynamo=False)
        return buf.getvalue()
    finally:
        onnx_proto_utils._add_onnxscript_fn = orig


class TestONNXControlFlow:
    def _match(self, model, xs, scripted=True, exact=True):
        data = _export_scripted(model, xs) if scripted else None
        if data is None:
            from torch.onnx._internal.torchscript_exporter import (
                onnx_proto_utils,
            )

            orig = onnx_proto_utils._add_onnxscript_fn
            onnx_proto_utils._add_onnxscript_fn = lambda mb, co: mb
            try:
                buf = io.BytesIO()
                torch.onnx.export(model, tuple(xs), buf,
                                  input_names=[f"x{i}" for i in range(len(xs))],
                                  output_names=["y"], dynamo=False)
                data = buf.getvalue()
            finally:
                onnx_proto_utils._add_onnxscript_fn = orig
        sd = import_onnx(data)
        feeds = {f"x{i}": v.numpy() for i, v in enumerate(xs)}
        out = np.asarray(sd.output(feeds, ["y"])["y"])
        with torch.no_grad():
            golden = model(*xs).numpy()
        if exact:
            np.testing.assert_array_equal(out, golden)
        else:
            np.testing.assert_allclose(out, golden, atol=1e-5, rtol=1e-5)

    def test_for_loop(self):
        self._match(_ForLoopNet(), [torch.randn(2, 3)])

    def test_while_loop_data_dependent(self):
        # INT64_MAX trip count + dynamic cond: 5 iterations at this input
        self._match(_WhileLoopNet(), [torch.ones(2, 3)])

    def test_if_both_branches(self):
        self._match(_CondNet(), [torch.randn(2, 3) + 5.0])
        self._match(_CondNet(), [torch.randn(2, 3) - 9.0])

    def test_greedy_decode_loop(self):
        torch.manual_seed(0)
        m = _GreedyDecode().eval()
        self._match(m, [torch.randint(0, 20, (2, 1)), torch.randn(2, 16)])

    def test_einsum(self):
        self._match(_EinsumNet(),
                    [torch.randn(2, 3, 4), torch.randn(2, 4, 5)],
                    scripted=False, exact=False)

    def test_op_tail(self):
        torch.manual_seed(1)
        self._match(_OpTailNet(), [torch.randn(3, 6)], scripted=False,
                    exact=False)

    def test_rule_count_floor(self):
        from deeplearning4j_tpu.imports.onnx_import import _ORULES

        assert len(_ORULES) >= 110, len(_ORULES)


class TestControlFlowSerialization:
    """Round-4: imported control-flow models SERIALIZE (structured
    __cf_* nodes carry their sub-graphs as specs — the closure-based
    custom_op path could not save). Reference parity: SameDiff .fb
    round-trips TFGraphMapper-imported control flow (path-cite)."""

    def test_greedy_decode_save_load_matches(self, tmp_path):
        torch.manual_seed(0)
        m = _GreedyDecode().eval()
        tok0 = torch.randint(0, 20, (2, 1))
        h0 = torch.randn(2, 16)
        data = _export_scripted(m, [tok0, h0])
        sd = import_onnx(data)
        feeds = {"x0": tok0.numpy(), "x1": h0.numpy()}
        ref = np.asarray(sd.output(feeds, ["y"])["y"])

        from deeplearning4j_tpu.samediff import SameDiff

        p = str(tmp_path / "greedy.sdz")
        sd.save(p)
        sd2 = SameDiff.load(p)
        out = np.asarray(sd2.output(feeds, ["y"])["y"])
        np.testing.assert_array_equal(out, ref)

    def test_while_loop_save_load_matches(self, tmp_path):
        m = _WhileLoopNet()
        x = torch.ones(2, 3)
        data = _export_scripted(m, [x])
        sd = import_onnx(data)
        ref = np.asarray(sd.output({"x0": x.numpy()}, ["y"])["y"])

        from deeplearning4j_tpu.samediff import SameDiff

        p = str(tmp_path / "while.sdz")
        sd.save(p)
        sd2 = SameDiff.load(p)
        out = np.asarray(sd2.output({"x0": x.numpy()}, ["y"])["y"])
        np.testing.assert_array_equal(out, ref)


class TestONNXDynamicBatch:
    """torch dynamic_axes exports (round 4): feed-forward architectures
    import once and run at ANY batch size (the Shape rule folds dynamic
    dims as -1 sentinels that resolve in Reshape targets); graphs that
    build runtime STATE shapes from a dynamic dim (torch RNN initial
    states) are rejected loudly at import instead of silently baking
    batch=1."""

    def _export_dynamic(self, model, x):
        from torch.onnx._internal.torchscript_exporter import (
            onnx_proto_utils,
        )

        orig = onnx_proto_utils._add_onnxscript_fn
        onnx_proto_utils._add_onnxscript_fn = lambda mb, co: mb
        try:
            buf = io.BytesIO()
            torch.onnx.export(
                model, (x,), buf, input_names=["x"], output_names=["y"],
                dynamic_axes={"x": {0: "batch"}, "y": {0: "batch"}},
                dynamo=False)
            return buf.getvalue()
        finally:
            onnx_proto_utils._add_onnxscript_fn = orig

    def test_resnet18_runs_at_two_batch_sizes(self):
        torch.manual_seed(0)
        m = _ResNet18().eval()
        sd = import_onnx(self._export_dynamic(m, torch.randn(2, 3, 64, 64)))
        for b in (2, 5):
            x = torch.randn(b, 3, 64, 64)
            out = np.asarray(sd.output({"x": x.numpy()}, ["y"])["y"])
            with torch.no_grad():
                golden = m(x).numpy()
            np.testing.assert_allclose(out, golden, atol=1e-4, rtol=1e-4)

    def test_rnn_state_from_dynamic_dim_rejected_loudly(self):
        torch.manual_seed(0)
        m = _LSTMSeq().eval()
        data = self._export_dynamic(m, torch.randint(0, 50, (2, 12)))
        with pytest.raises(NotImplementedError, match="dynamic dim"):
            import_onnx(data)

    def test_slice_end_from_dynamic_dim_rejected_loudly(self):
        """Round-5 regression (advisor repro): x[:x.shape[0]] exported with
        dynamic_axes folds the batch dim as the -1 sentinel, which reached
        Slice `ends` as a plain negative index and silently dropped the
        last row. const() now rejects sentinel-derived values for every
        consumer except Reshape."""

        class _SliceByShape(torch.nn.Module):
            def forward(self, x):
                return x[: x.shape[0]] + 1.0

        data = self._export_dynamic(
            _SliceByShape().eval(), torch.randn(2, 4))
        with pytest.raises(NotImplementedError, match="dynamic"):
            import_onnx(data)

    def test_static_dim_extracted_from_dynamic_shape_still_imports(self):
        """Provenance taint alone would over-reject: x.shape[1]//2 derives
        from the dynamic-batch Shape fold but its VALUE is static. The
        dependence probe (evaluate with two sentinel substitutions) keeps
        this importable while still rejecting true batch-dependence."""

        class _HalfSlice(torch.nn.Module):
            def forward(self, x):
                return x[:, : x.shape[1] // 2] * 2.0

        m = _HalfSlice().eval()
        sd = import_onnx(self._export_dynamic(m, torch.randn(2, 6)))
        for b in (2, 5):
            x = torch.randn(b, 6)
            out = np.asarray(sd.output({"x": x.numpy()}, ["y"])["y"])
            np.testing.assert_allclose(out, m(x).numpy(), atol=1e-6)

    def test_runtime_consumer_of_static_dim_imports(self):
        """Round-5 regression (review finding): when the static-extracted
        dim feeds RUNTIME arithmetic (Mul) instead of going through
        const(), the import-time output check used provenance only and
        wrongly rejected the graph. The refined check probes the
        static/runtime boundary value and keeps this importable."""

        class _ScaleByWidth(torch.nn.Module):
            def forward(self, x):
                return x * x.shape[1]

        m = _ScaleByWidth().eval()
        sd = import_onnx(self._export_dynamic(m, torch.randn(2, 6)))
        for b in (2, 4):
            x = torch.randn(b, 6)
            out = np.asarray(sd.output({"x": x.numpy()}, ["y"])["y"])
            np.testing.assert_allclose(out, m(x).numpy(), atol=1e-6)

    def test_runtime_consumer_of_batch_dim_still_rejected(self):
        """Counterpart: the BATCH dim's value reaching runtime arithmetic
        is genuinely batch-dependent — must stay a loud rejection, not a
        silent -1."""

        class _ScaleByBatch(torch.nn.Module):
            def forward(self, x):
                return x * x.shape[0]

        data = self._export_dynamic(_ScaleByBatch().eval(),
                                    torch.randn(2, 6))
        with pytest.raises(NotImplementedError, match="dynamic|sentinel"):
            import_onnx(data)


class TestTFDynamicBatch:
    @staticmethod
    def _freeze_dynamic(fn):
        from tensorflow.python.framework.convert_to_constants import (
            convert_variables_to_constants_v2,
        )

        conc = tf.function(fn).get_concrete_function(
            tf.TensorSpec([None, 6], tf.float32))
        frozen = convert_variables_to_constants_v2(conc)
        in_name = frozen.inputs[0].name.split(":")[0]
        out_name = frozen.outputs[0].name
        return frozen.graph.as_graph_def(), frozen, in_name, out_name

    def test_shape_n_static_dim_imports_batch_dim_rejected(self, rng):
        """Round-5 regression (review finding): the ShapeN rule folded the
        dynamic batch dim as a -1 constant WITHOUT the Shape rule's taint,
        so batch-dependent values silently reached runtime arithmetic.
        ShapeN now taints like Shape: the static-dim consumer imports (and
        matches TF at two batch sizes), the batch-dim consumer fails
        loudly."""

        def uses_static_dim(x):
            s = tf.raw_ops.ShapeN(input=[x, x])[0]
            return x * tf.cast(s[1], tf.float32)

        gd, frozen, in_name, out_name = self._freeze_dynamic(uses_static_dim)
        sd = import_graph_def(gd)
        key = sd.tf_name_map[out_name]
        for b in (2, 5):
            x = rng.normal(size=(b, 6)).astype(np.float32)
            res = np.asarray(sd.output({in_name: x}, [key])[key])
            np.testing.assert_allclose(res, np.asarray(frozen(
                tf.constant(x))[0]), atol=1e-5)

        def uses_batch_dim(x):
            s = tf.raw_ops.ShapeN(input=[x, x])[0]
            return x * tf.cast(s[0], tf.float32)

        gd2, _, in2, out2 = self._freeze_dynamic(uses_batch_dim)
        with pytest.raises(NotImplementedError, match="dynamic|sentinel"):
            sd2 = import_graph_def(gd2)
            key2 = sd2.tf_name_map[out2]
            sd2.output({in2: np.zeros((2, 6), np.float32)}, [key2])

    def test_imported_graph_runs_at_two_batch_sizes(self, rng):
        """TF frozen graphs traced with batch=None import once and run at
        any batch size (the keras Pack/StridedSlice reshape pattern folds
        the dynamic dim as -1)."""
        tf.keras.utils.set_random_seed(7)
        model = tf.keras.applications.MobileNetV2(
            weights=None, include_top=False, input_shape=(64, 64, 3),
            pooling="avg")
        gd, frozen, in_name, out_name = _freeze_keras(model)
        sd = import_graph_def(gd)
        key = sd.tf_name_map[out_name]
        for b in (2, 5):
            x = rng.normal(size=(b, 64, 64, 3)).astype(np.float32)
            golden = frozen(tf.constant(x))
            if isinstance(golden, (list, tuple)):
                golden = golden[0]
            res = np.asarray(sd.output({in_name: x}, [key])[key])
            np.testing.assert_allclose(res, np.asarray(golden), atol=1e-4,
                                       rtol=1e-4)
