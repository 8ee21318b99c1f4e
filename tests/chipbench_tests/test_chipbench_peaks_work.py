"""The table of peaks and the shape arithmetic, against hand-worked values."""

import pytest

from chipbench import manifest, peaks, work

MAN = manifest.load_manifest()
RESNET = manifest.Cell(MAN, "resnet50-fit-staged").cfg
BERT = manifest.Cell(MAN, "bertL-doc-closed").cfg
REF = manifest.module_from("reference", "resnet50")


def test_v5e_peaks_are_the_data_sheets():
    p = peaks.peaks_of("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "", "TPU v5"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_of(kind)


def test_resnet50_forward_flops_by_hand():
    # stem 7x7x3x64 at 112^2; per stage (1x1, 3x3, 1x1) at 56/28/14/7 with
    # one projection each; classifier 2048x1000; x2 per multiply-add
    total = 2 * 7 * 7 * 3 * 64 * 112 * 112
    c_in = 64
    for size, blocks, (f1, f2, f3) in ((56, 3, (64, 64, 256)),
                                       (28, 4, (128, 128, 512)),
                                       (14, 6, (256, 256, 1024)),
                                       (7, 3, (512, 512, 2048))):
        for i in range(blocks):
            total += 2 * size * size * (c_in * f1 + 9 * f1 * f2 + f2 * f3)
            if i == 0:
                total += 2 * size * size * c_in * f3
            c_in = f3
    total += 2 * 2048 * 1000
    assert REF.forward_flops(RESNET) == total == 7_715_946_496
    assert work.train_flops_per_example(RESNET) == 3 * total


def test_resnet50_flops_scale_with_the_image():
    big = dict(RESNET, image_size=448)
    ratio = REF.forward_flops(big) / REF.forward_flops(RESNET)
    assert 3.99 < ratio < 4.0     # the classifier does not scale


def test_bert_large_parameters_by_hand():
    per_layer = 4 * 1024 * 1024 + 2 * 1024 * 4096
    assert work.decoder_matmul_params(BERT) == 24 * per_layer + 1024 * 30522
    # + biases 4*1024+4096+1024, four LayerNorm vectors, embeddings, head bias
    extra = 24 * (4 * 1024 + 4096 + 1024 + 4 * 1024)
    emb = (30522 + 512 + 2) * 1024 + 2 * 1024
    assert work.decoder_params(BERT) == (24 * per_layer + extra + emb
                                         + 1024 * 30522 + 30522)
    assert 365e6 < work.decoder_params(BERT) < 366e6


def test_kv_bytes_per_token_is_the_issues_196608():
    assert work.kv_bytes_per_token(BERT) == 2 * 24 * 1024 * 4 == 196_608


def test_decode_step_bytes_by_hand():
    weights = (24 * 12 * 1024 * 1024 + 1024 * 30522) * 4
    assert work.decode_step_bytes(BERT, 0) == weights
    assert work.decode_step_bytes(BERT, 1000) == weights + 1000 * 196_608


def test_request_flops_by_hand():
    # 3 prompt tokens, 2 served: tokens 0..3 pass the layers, the head runs
    # twice, token i attends i + 1 keys
    n = 4
    layers = n * 2 * 24 * 12 * 1024 * 1024
    attn = 24 * 4 * 1024 * (1 + 2 + 3 + 4)
    head = 2 * 2 * 1024 * 30522
    assert work.decoder_request_flops(BERT, 3, 2) == layers + attn + head


def test_an_unknown_reference_brings_its_own_count():
    with pytest.raises(FileNotFoundError, match="add that file"):
        work.train_flops_per_example(dict(RESNET, reference="not_there"))


def test_work_knows_no_model_by_name():
    import inspect

    assert "resnet" not in inspect.getsource(work).lower()
