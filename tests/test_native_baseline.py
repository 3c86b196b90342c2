"""C++ scalar SPF baseline parity with the Python oracle."""

import shutil

import numpy as np
import pytest

from holo_tpu.spf.backend import ScalarSpfBackend
from holo_tpu.spf.synth import random_ospf_topology, whatif_link_failure_masks

pytestmark = pytest.mark.skipif(shutil.which("g++") is None, reason="needs g++")


@pytest.mark.parametrize("seed", range(4))
def test_native_matches_python_oracle(seed):
    from holo_tpu.native_build import native_spf

    topo = random_ospf_topology(n_routers=30, n_networks=8, extra_p2p=50, seed=seed)
    dist, parent, hops, nh = native_spf(topo)
    ref = ScalarSpfBackend().compute(topo)
    np.testing.assert_array_equal(ref.dist, dist)
    np.testing.assert_array_equal(ref.parent, parent)
    np.testing.assert_array_equal(ref.hops, hops)
    # nh is a 64-bit mask; reference words are uint32[N, 2].
    ref64 = ref.nexthop_words[:, 0].astype(np.uint64) | (
        ref.nexthop_words[:, 1].astype(np.uint64) << np.uint64(32)
    )
    np.testing.assert_array_equal(ref64, nh)


def test_native_batch_masks():
    from holo_tpu.native_build import native_spf_batch_dist

    topo = random_ospf_topology(n_routers=20, n_networks=4, seed=7)
    masks = whatif_link_failure_masks(topo, n_scenarios=6, seed=1)
    dists = native_spf_batch_dist(topo, masks)
    for i in range(masks.shape[0]):
        ref = ScalarSpfBackend().compute(topo, masks[i])
        np.testing.assert_array_equal(ref.dist, dists[i])


def test_library_is_keyed_on_host_so_a_copied_build_is_not_loaded(
    monkeypatch, tmp_path
):
    """ISSUE 21: the objects are -march=native, so a native/build/
    copied from another machine must be rebuilt, never loaded."""
    from holo_tpu import native_build as nb

    here = nb._ensure("libspf_baseline", ["spf_baseline.cpp"])
    copied = tmp_path / here.name
    copied.write_bytes(b"built on another CPU")
    monkeypatch.setattr(nb, "BUILD", tmp_path)
    assert nb._ensure("libspf_baseline", ["spf_baseline.cpp"]) == copied
    monkeypatch.setattr(nb, "_host_id", lambda: "another-host")
    rebuilt = nb._ensure("libspf_baseline", ["spf_baseline.cpp"])
    assert rebuilt != copied and rebuilt.stat().st_size > 1000
