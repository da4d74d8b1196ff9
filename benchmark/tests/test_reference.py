"""The plain RS(10,4) reference against a hand-worked stripe and against the
properties that define the code."""

import numpy as np
import pytest

from benchlib import reference as R


def test_field_is_gf256_with_0x11d():
    assert R.gf_mul(2, 0x80) == 0x1D          # x * x^7 = x^8 = x^4+x^3+x^2+1
    assert R.gf_mul(3, 7) == 9                # (x+1)(x^2+x+1) = x^3+1
    assert R.gf_mul(0x53, R.gf_inv(0x53)) == 1
    assert all(R.gf_mul(a, R.gf_inv(a)) == 1 for a in range(1, 256))


def test_matrix_is_the_one_upstream_builds():
    m = R.coding_matrix()
    assert np.array_equal(m[:10], np.eye(10, dtype=np.uint8))
    # klauspost/reedsolomon's RS(10,4) parity rows (its matrix is the
    # Vandermonde matrix times the inverse of its top square)
    assert m[10].tolist() == [129, 150, 175, 184, 210, 196, 254, 232, 3, 2]
    assert m[11].tolist() == [150, 129, 184, 175, 196, 210, 232, 254, 2, 3]
    assert m[12].tolist() == [191, 214, 98, 10, 6, 111, 223, 183, 5, 4]
    assert m[13].tolist() == [214, 191, 10, 98, 111, 6, 183, 223, 4, 5]


def test_hand_worked_stripe():
    # one byte column: only data shards 8 and 9 are non-zero
    data = np.zeros((10, 1), dtype=np.uint8)
    data[8, 0], data[9, 0] = 1, 2
    parity = R.apply_matrix(R.coding_matrix()[10:], data)
    # row 10: 3*1 ^ 2*2 = 3 ^ 4 = 7; row 11: 2*1 ^ 3*2 = 2 ^ 6 = 4
    # row 12: 5*1 ^ 4*2 = 5 ^ 8 = 13; row 13: 4*1 ^ 5*2 = 4 ^ 10 = 14
    assert parity[:, 0].tolist() == [7, 4, 13, 14]


@pytest.mark.parametrize("lost", [[3], [0, 13], [10, 11, 12, 13], [1, 4, 7, 12]])
def test_any_ten_rebuild_the_rest(lost):
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    m = R.coding_matrix()
    shards = R.apply_matrix(m, data)
    present = [s for s in range(14) if s not in lost]
    rows = R.decode_rows(m, present, lost)
    assert np.array_equal(R.apply_matrix(rows, shards[present[:10]]), shards[lost])


def test_cauchy_control_is_mds_and_differs():
    c, m = R.cauchy_matrix(), R.coding_matrix()
    assert not np.array_equal(c[10:], m[10:])
    for lost in ([0, 1, 2, 3], [9, 10, 11, 12], [3, 6, 9, 13]):
        R.mat_inv(c[[s for s in range(14) if s not in lost]])  # raises if singular


def test_layout(tmp_path):
    assert R.shard_geometry(1073782792) == (0, 103)
    assert R.shard_file_size(1073782792) == 103 * 1048576
    assert R.shard_geometry(10 * 1048576) == (0, 1)
    assert R.shard_geometry(10 * 1048576 + 1) == (0, 2)
    dat = tmp_path / "v.dat"
    raw = np.random.default_rng(1).integers(0, 256, 10 * 1048576 + 5, dtype=np.uint8)
    raw.tofile(dat)
    want = R.expected_shards(str(dat))
    assert want.shape == (14, 2 * 1048576)
    assert np.array_equal(want[3, :1048576], raw[3 * 1048576:4 * 1048576])
    assert np.array_equal(want[0, 1048576:1048581], raw[-5:])
    assert not want[1, 1048576:].any()          # the padded tail
    want[12].tofile(tmp_path / "ec12")
    assert not R.file_differs(str(tmp_path / "ec12"), want[12])
    assert R.file_differs(str(tmp_path / "ec12"), want[13])
    assert R.file_differs(str(tmp_path / "absent"), want[13])


def test_agrees_with_the_programs_oracle():
    """A second witness, not a dependency: the reference imports nothing of
    the program, this test does."""
    gf256 = pytest.importorskip("seaweedfs_tpu.ops.gf256")
    assert np.array_equal(R.coding_matrix()[10:], gf256.parity_rows(10, 4))
