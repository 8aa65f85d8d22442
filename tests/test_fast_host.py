"""fast_host (the fast host oracle other tests compare against) is
bit-identical to refimpl."""

import numpy as np
import pytest

from celestia_app_tpu.utils import fast_host, refimpl


@pytest.mark.parametrize("k", [2, 8])
def test_fast_host_matches_refimpl(k):
    rng = np.random.default_rng(11 + k)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    ods[..., :29] = 0
    ods[..., 28] = 7  # uniform user namespace -> valid leaf ordering

    eds_f, rows_f, cols_f, root_f = fast_host.pipeline_fast(ods)
    eds_r, rows_r, cols_r, root_r = refimpl.pipeline_host(ods)

    np.testing.assert_array_equal(eds_f, eds_r)
    for a, b in zip(rows_f, rows_r):
        assert bytes(a) == bytes(b)
    for a, b in zip(cols_f, cols_r):
        assert bytes(a) == bytes(b)
    assert bytes(root_f) == bytes(root_r)
