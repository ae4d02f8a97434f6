"""The FLOP and byte functions against hand counts at toy widths."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]

from chipbench import flops, spec  # noqa: E402

DATA = BENCH / "tests" / "data"


def _cfg(name):
    cell = spec.find_cell(name, root=DATA, bench_dir=DATA)
    return cell.config.model, cell.config.meta


def test_dense_decode_against_a_hand_count():
    cfg, _ = _cfg("tiny-dense.chat")   # L 2, d 64, H 4, K 2, hd 16, ff 128, V 512
    # per layer: wq 64*64 + wk, wv 2*64*32 + wo 64*64 + MLP 3*64*128
    assert flops.dense_layer_matmul_params(cfg) == 36_864
    # two live slots attending 1 and 10 positions:
    # 2 * (2 * 36864 + 64 * 512) per token, plus 4 * L * H * hd per position
    assert flops.dense_decode_flops(cfg, [1, 10]) == 2 * 212_992 + 512 * 11
    # weights (2 layers with two norms each, final norm, unembedding) in
    # bf16, two embedding rows, K and V of 11 live and 2 new positions
    weights = (2 * (36_864 + 128) + 64 + 64 * 512) * 2
    assert flops.dense_decode_bytes(cfg, [1, 10]) == \
        weights + 2 * 64 * 2 + (2 * 2 * 2 * 16 * 2) * 13


def test_ssd_training_against_a_hand_count():
    cfg, meta = _cfg("tiny-ssm.elastic")  # d 64, di 128, H 16, N 16, P 8, V 256
    proj = 2 * (64 * 304 + 128 * 64) + 2 * 4 * 160
    intra = 2 * 8 * (16 + 16 * 8)
    states = 4 * 16 * 8 * 16
    fwd = 3 * (proj + intra + states) + 2 * 64 * 256
    assert flops.ssd_train_flops_per_token(cfg, 64) == pytest.approx(3 * fwd)


def test_published_minitron_decode_is_bound_by_memory():
    cell = spec.find_cell("minitron-8b.batch")
    cfg = cell.config.model
    ctx = [600] * 32
    t_flops = flops.dense_decode_flops(cfg, ctx) / 197e12
    t_bytes = flops.dense_decode_bytes(cfg, ctx) / 819e9
    assert t_bytes > 5 * t_flops
    # 8 layers and the unembedding are about 6 GB of bf16 weights
    assert 5.5e9 < flops.dense_decode_bytes(cfg, []) < 6.5e9
