"""Protocol-marshaled topologies (BASELINE configs 2+3): the
builders go through the real instance marshal paths and the engine
reproduces the scalar result bit-identically."""

import numpy as np

from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend


def test_ospfv3_multiarea_builder_parity():
    from holo_tpu.spf.synth_proto import ospfv3_multiarea_topologies

    topos = ospfv3_multiarea_topologies(n_routers=200, n_areas=4, seed=3)
    assert len(topos) == 4
    for topo in topos:
        assert topo.n_vertices == 51  # root + 50 per area
        s = ScalarSpfBackend().compute(topo)
        t = TpuSpfBackend().compute(topo)
        assert np.array_equal(s.dist, t.dist)
        assert np.array_equal(s.nexthop_words, t.nexthop_words)


def test_isis_l1l2_builder_parity_and_ecmp():
    from holo_tpu.spf.synth_proto import isis_l1l2_topologies

    # The builder itself asserts the 64-way (here 16-way) ECMP fan-out
    # in the L2 instance's own route table.
    topos = isis_l1l2_topologies(n_l2=360, n_l1=40, ecmp_width=16, seed=2)
    assert len(topos) == 2
    for topo in topos:
        s = ScalarSpfBackend().compute(topo)
        t = TpuSpfBackend().compute(topo)
        assert np.array_equal(s.dist, t.dist)
        assert np.array_equal(s.nexthop_words, t.nexthop_words)
