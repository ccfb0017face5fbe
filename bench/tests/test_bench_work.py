"""Operation and byte counts at real widths, and the peak table."""

import json
import pathlib

import pytest

from bench import peaks, work

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def sizes(name):
    return work.layer_sizes(json.loads((CONFIGS / f"{name}.json").read_text()))


@pytest.mark.parametrize("name,want", [
    ("mrf-fpga", (64, 64, 64, 32, 16, 16, 16, 2)),
])
def test_layer_sizes_are_the_published_widths(name, want):
    assert sizes(name) == want


@pytest.mark.parametrize("name,macs,params,fwd,train", [
    # 64*64 + 64*64 + 64*32 + 32*16 + 16*16 + 16*16 + 16*2
    ("mrf-fpga", 11296, 11506, 22592, 59584),
])
def test_operation_counts(name, macs, params, fwd, train):
    s = sizes(name)
    assert work.macs(s) == macs
    assert work.n_params(s) == params
    assert work.forward_ops(s) == fwd
    # forward 2M, weight gradients 2M, input gradients 2(M - first layer)
    assert work.train_ops_per_row(s) == train


def test_byte_counts():
    f = sizes("mrf-fpga")
    # rows x (64 in + 2 out) x 4 B, and the state read and written per launch
    assert work.train_kernel_bytes(f, 256, 1, "sgd") == 256 * 66 * 4 + 2 * 11506 * 4
    # Adam's two moments are read and written with the weights
    assert (work.train_kernel_bytes(f, 4096, 16, "adam")
            == 4096 * 66 * 4 + 16 * 2 * 3 * 11506 * 4)


def test_roofline_share_takes_the_longer_bound():
    share, bound = work.roofline_share(ops=2e12, nbytes=1e9, seconds=0.1,
                                       ops_per_s=1e14, bytes_per_s=1e12)
    assert bound == "compute" and share == pytest.approx(20.0)
    share, bound = work.roofline_share(ops=1e9, nbytes=1e11, seconds=1.0,
                                       ops_per_s=1e14, bytes_per_s=1e12)
    assert bound == "memory" and share == pytest.approx(10.0)


def test_peaks_of_tpu_v5e():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["int8_ops_per_s"], p["hbm_bytes_per_s"],
            p["hbm_bytes"]) == (197e12, 393e12, 819e9, 16e9)
    assert p["source"] == "Google Cloud documentation, TPU v5e"


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks_for(kind)
