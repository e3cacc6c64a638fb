"""The yardstick's operation and byte counts against hand counts."""
import json

import pytest

from chipbench_tiny import ROOT


def _fam_cfg(name):
    """The configuration's family file and its ArchConfig."""
    from chipbench import spec

    conf = json.loads(
        (ROOT / "chipbench" / "configs" / f"{name}.json").read_text())
    fam = spec.family_of(conf)
    return fam, fam.arch_config(conf)


def test_matmul_params_nemotron_l2():
    fam, cfg = _fam_cfg("nemotron3-8b-l2")
    per_layer = 4096 * 3 * 4096 + 4096 * 4096 + 2 * 4096 * 16384
    assert fam.matmul_params_per_layer(cfg) == per_layer == 201_326_592
    # Two layers and the 32,000-column head; the embedding is a lookup.
    assert fam.matmul_params(cfg) == 2 * per_layer + 4096 * 32000
    assert fam.matmul_params(cfg) == 533_725_184


def test_train_step_flops_hand_count():
    fam, cfg = _fam_cfg("nemotron3-8b-l2")
    tokens = 4 * 2048
    attn = 4 * 2 * (2 * 2048 * 2048 * 32 * 128)  # batch, layers, fwd
    want = 3 * (2 * 533_725_184 * tokens + attn)
    assert fam.train_step_flops(cfg, 4, 2048) == pytest.approx(want)
    # A 482.5 ms step, as measured on a TPU v5e, at one chip's bf16 peak.
    mfu = want / 0.4825 / 197e12
    assert 0.28 < mfu < 0.29


def test_fake_quant_event_bytes():
    from chipbench import counts

    # The qkv activation of one step: (4*2048, 4096) bf16, read + written.
    assert counts.fake_quant_bytes((8192, 4096)) == 2 * 8192 * 4096 * 2


def test_mixed_gemm_bytes():
    from chipbench import counts

    # Minitron's fc1 at 16 decode rows: (9216, 3072) weight, 1 B/elt,
    # one tag byte and one f32 scale per 128x128 block.
    blocks = (9216 // 128) * (3072 // 128)
    want = 9216 * 3072 + 5 * blocks + 16 * 3072 * 2 + 16 * 9216 * 2
    assert counts.mixed_gemm_bytes(16, 3072, 9216) == want


def test_decode_step_flops():
    fam, cfg = _fam_cfg("minitron-4b")
    got = fam.decode_step_flops(cfg, [100, 300])
    mm = 2 * fam.matmul_params(cfg) * 2
    attn = 4 * 32 * 24 * 128 * (100 + 300)
    assert got == mm + attn


def test_unknown_device_kind_is_an_error():
    from chipbench import counts

    assert counts.peaks("TPU v5 lite")["peak_flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_hlo_shape_bytes():
    from chipbench import counts

    text = ("%custom-call.3 = bf16[8192,4096]{1,0} custom-call("
            "bf16[8192,4096]{1,0} %p.1), custom_call_target=\"tpu\"")
    assert counts.hlo_io_bytes(text) == 2 * 8192 * 4096 * 2
