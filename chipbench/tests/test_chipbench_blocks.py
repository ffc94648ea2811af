"""The host check of the sampled blocks (``check.block_faults``) on the
``tiny`` graph: sound schedules read 0 at one worker and at four, and
each planted fault in the sampling or the block construction reads more
than 0, in a full or in the partial last batch."""
import numpy as np
import pytest

from chipbench import check, harness
from chipbench.tests import _tiny


def schedules(workers: int, epochs: int = 2):
    from repro.core import build_schedule
    from repro.graph import KHopSampler, partition_graph

    conf = _tiny.tiny_config()
    g, arrays = harness.build_graph(conf)
    pg = partition_graph(g, workers, "metis")
    sampler = KHopSampler(g, fanouts=conf["fanouts"], batch_size=16)
    ws = [build_schedule(sampler, pg, worker=w, s0=2 ** 33 + 11,
                         num_epochs=epochs, n_hot=conf["n_hot"])
          for w in range(workers)]
    flats = [[w.epoch(e).flat for w in ws] for e in range(epochs)]
    return arrays, flats, conf["fanouts"]


@pytest.mark.parametrize("workers", [1, 4])
def test_sound_blocks_read_zero(workers):
    assert check.block_faults(*schedules(workers)) == 0


def _last_batch_edge(f, layer: int) -> int:
    """The first valid edge of the epoch's last (partial) batch."""
    lo = int(f.edge_starts[layer][-2])
    return lo + int(np.flatnonzero(f.edge_mask[layer][lo:])[0])


def plant(fault: str, arrays, flats):
    f = flats[1][0]
    if fault == "edge":             # a source that is not an in-neighbour
        i = _last_batch_edge(f, 0)
        f.edge_src[0][i] = (f.edge_src[0][i] + 1) % f.num_dst[0][-1]
    elif fault == "dst":            # an edge moved to another dst row
        i = _last_batch_edge(f, 1)
        f.edge_dst[1][i] = (f.edge_dst[1][i] + 1) % f.num_dst[1][-1]
    elif fault == "seed":           # a seed that is no train node
        j = int(np.flatnonzero(~arrays["train_mask"])[0])
        f.seeds[-1] = j
        f.input_nodes[f.input_starts[-2]
                      + f.seed_starts[-1] - f.seed_starts[-2] - 1] = j
    elif fault == "row":            # an input row given twice
        f.input_nodes[f.input_starts[-1] - 1] = f.input_nodes[
            f.input_starts[-2]]
    elif fault == "mask":           # a real edge masked out
        f.edge_mask[0][_last_batch_edge(f, 0)] = False
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["edge", "dst", "seed", "row", "mask"])
def test_each_planted_fault_reads_above_zero(fault):
    arrays, flats, fanouts = schedules(1)
    f = flats[1][0]
    assert f.seed_starts[-1] - f.seed_starts[-2] < 16   # partial batch
    plant(fault, arrays, flats)
    assert check.block_faults(arrays, flats, fanouts) > 0
