"""The FLOP and byte counters against hand counts for one tiny batch."""
import functools

import numpy as np
import pytest

from chipbench import counts, harness

#: widths [4, 3, 2]
CONFIG = {"model": "sage", "feat_dim": 4, "hidden_dim": 3,
          "num_classes": 2, "num_layers": 2}


def sage_counts(flat):
    flops = functools.partial(harness.load_model("sage").epoch_flops, CONFIG)
    return counts.epoch_counts(flat, CONFIG["feat_dim"], flops)


def one_batch_epoch():
    """One batch: 2 seeds, 5 input rows; layer 0 has 3 dst rows and 4
    edges of which 3 are valid, layer 1 has 2 dst rows and 2 edges."""
    from repro.graph import FlatEpoch

    z = np.array([0, 2])
    return FlatEpoch(
        epoch=0, worker=0, seeds=np.array([7, 9]), seed_starts=z,
        input_nodes=np.array([7, 9, 3, 4, 5]),
        input_starts=np.array([0, 5]),
        num_dst=np.array([[3], [2]]),
        edge_src=[np.array([3, 4, 2, 0], np.int32),
                  np.array([2, 1], np.int32)],
        edge_dst=[np.array([0, 1, 2, 0], np.int32),
                  np.array([0, 1], np.int32)],
        edge_mask=[np.array([True, True, True, False]),
                   np.array([True, True])],
        edge_starts=[np.array([0, 4]), np.array([0, 2])])


def test_hand_counts_for_one_batch():
    c = sage_counts(one_batch_epoch())
    # layer 0: 3 dst rows x (2*4*3) x 2 products + 3 valid edges x 4 adds
    # layer 1: 2 dst rows x (2*3*2) x 2 products + 2 edges x 3 adds
    forward = 3 * 24 * 2 + 3 * 4 + 2 * 12 * 2 + 2 * 3
    assert c["flops"] == pytest.approx(3 * forward)       # 630
    assert c["assemble_bytes"] == 5 * (2 * 4 * 4 + 4)      # 180
    assert (c["seeds"], c["rows"]) == (2, 5)


def test_counts_add_up_over_an_epoch():
    a = sage_counts(one_batch_epoch())
    total = counts.add(counts.add({}, a), a)
    assert total == {k: 2 * v for k, v in a.items()}
