"""RS(10,4) codec: field math, matrix construction, cross-backend byte identity."""

import numpy as np
import pytest

from seaweedfs_tpu.ops import device, gf256, rs_kernel
from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.stats import default_registry, trace


class TestGF256:
    def test_field_basics(self):
        assert gf256.gf_mul(0, 5) == 0
        assert gf256.gf_mul(1, 77) == 77
        assert gf256.gf_mul(2, 2) == 4
        assert gf256.gf_mul(0x80, 2) == 0x1D  # wraps through poly 0x11D
        for a in (1, 2, 5, 77, 200, 255):
            assert gf256.gf_div(gf256.gf_mul(a, 13), 13) == a
            assert gf256.gf_mul(a, gf256.gf_div(1, a)) == 1

    def test_gf_exp(self):
        assert gf256.gf_exp(0, 0) == 1  # klauspost galExp convention
        assert gf256.gf_exp(0, 5) == 0
        assert gf256.gf_exp(2, 8) == gf256.gf_mul(gf256.gf_exp(2, 7), 2)

    def test_mat_invert(self):
        rng = np.random.RandomState(0)
        for _ in range(5):
            m = rng.randint(0, 256, size=(6, 6)).astype(np.uint8)
            try:
                inv = gf256.mat_invert(m)
            except np.linalg.LinAlgError:
                continue
            assert np.array_equal(gf256.mat_mul(m, inv), gf256.identity(6))

    def test_rs_matrix_identity_top(self):
        m = gf256.rs_matrix(10, 4)
        assert m.shape == (14, 10)
        assert np.array_equal(m[:10], gf256.identity(10))
        # any 10 rows of the encoding matrix must be invertible (MDS property)
        rng = np.random.RandomState(1)
        for _ in range(10):
            rows = sorted(rng.choice(14, size=10, replace=False))
            gf256.mat_invert(m[rows])  # must not raise

    def test_bit_matrix_equiv(self):
        """bit-plane expansion reproduces the field product for single bytes."""
        m = np.array([[3, 7], [2, 9]], dtype=np.uint8)
        a = gf256.bit_matrix(m)  # (16, 16)
        rng = np.random.RandomState(2)
        x = rng.randint(0, 256, size=(2, 32)).astype(np.uint8)
        want = gf256.gf_matmul_bytes(m, x)
        bits = ((x.T[:, :, None] >> np.arange(8)) & 1).reshape(32, 16)
        ybits = (bits @ a) & 1
        got = (ybits.reshape(32, 2, 8) << np.arange(8)).sum(-1).astype(np.uint8).T
        assert np.array_equal(want, got)


class TestRSCodec:
    @pytest.fixture(scope="class")
    def data(self):
        rng = np.random.RandomState(7)
        return rng.randint(0, 256, size=(10, 4096)).astype(np.uint8)

    def test_encode_backends_identical(self, data):
        outs = {}
        for backend in ("numpy", "native", "jax"):
            try:
                outs[backend] = RSCodec(backend=backend).encode(data)
            except Exception as e:
                if backend == "numpy":
                    raise
                pytest.skip(f"backend {backend} unavailable: {e}")
        base = outs["numpy"]
        for name, out in outs.items():
            assert np.array_equal(out, base), f"{name} parity differs from numpy"

    def test_parity_nonzero(self, data):
        parity = RSCodec(backend="numpy").encode(data)
        assert parity.shape == (4, 4096)
        assert parity.any()

    @pytest.mark.parametrize("missing", [[0], [13], [0, 5], [3, 11], [0, 1, 2, 3], [10, 11, 12, 13], [0, 4, 10, 13]])
    def test_reconstruct(self, data, missing):
        codec = RSCodec(backend="numpy")
        shards = codec.encode_all(data)
        surviving = {
            i: shards[i] for i in range(14) if i not in missing
        }
        recovered = codec.reconstruct(surviving)
        assert sorted(recovered) == sorted(missing)
        for i in missing:
            assert np.array_equal(recovered[i], shards[i]), f"shard {i} mismatch"

    def test_reconstruct_jax_matches(self, data):
        codec_np = RSCodec(backend="numpy")
        codec_jax = RSCodec(backend="jax")
        shards = codec_np.encode_all(data)
        surviving = {i: shards[i] for i in range(14) if i not in (2, 7, 11)}
        r_np = codec_np.reconstruct(surviving)
        r_jax = codec_jax.reconstruct(surviving)
        for k in r_np:
            assert np.array_equal(r_np[k], r_jax[k])

    def test_too_few_shards_raises(self, data):
        codec = RSCodec(backend="numpy")
        shards = codec.encode_all(data)
        surviving = {i: shards[i] for i in range(9)}  # only 9 < 10
        with pytest.raises(ValueError):
            codec.reconstruct(surviving)

    def test_verify(self, data):
        codec = RSCodec(backend="numpy")
        shards = codec.encode_all(data)
        assert codec.verify(shards)
        shards[12, 100] ^= 1
        assert not codec.verify(shards)

    def test_odd_lengths(self):
        """non-multiple-of-128 lengths must work (tail blocks)."""
        rng = np.random.RandomState(3)
        for n in (1, 7, 100, 255, 1000):
            data = rng.randint(0, 256, size=(10, n)).astype(np.uint8)
            p_np = RSCodec(backend="numpy").encode(data)
            p_jax = RSCodec(backend="jax").encode(data)
            assert np.array_equal(p_np, p_jax)


# --- the jax door: host bytes in, one device program, host bytes out ----------
# tests/test_rs_pallas.py runs the same checks through the Pallas form.


def door_widths(tile: int) -> tuple[int, ...]:
    """Around a tile and around the read cell's intervals: 8191, 8192, 8193,
    27720, 65576 and 73728 at the kernel's own tile."""
    return (1, tile - 1, tile, tile + 1, 3 * tile + 3144 * tile // 8192,
            8 * tile + 40, 9 * tile)


def device_programs() -> float:
    page = default_registry().render()
    return sum(float(line.split()[-1]) for line in page.splitlines()
               if line.startswith(trace.EC_DEVICE_PROGRAMS))


def compile_requests() -> int:
    return device.report()["compiles"]["requests"]


def check_door_against_oracle(n: int, lost: int) -> None:
    """encode and reconstruct of host bytes through RSCodec(backend="jax"),
    byte for byte against the numpy oracle."""
    rng = np.random.RandomState(n * 16 + lost)
    data = rng.randint(0, 256, size=(10, n), dtype=np.uint8)
    oracle, codec = RSCodec(backend="numpy"), RSCodec(backend="jax")
    shards = oracle.encode_all(data)
    parity = codec.encode(data)
    assert parity.shape == (4, n) and parity.dtype == np.uint8
    assert np.array_equal(parity, shards[10:])
    surviving = {i: shards[i] for i in range(14) if i != lost}
    got = codec.reconstruct(surviving, targets=[lost])
    assert list(got) == [lost] and got[lost].shape == (n,)
    assert np.array_equal(got[lost], shards[lost])


def check_one_bucket_one_program(n_first: int, n_second: int) -> None:
    """Two host-input reconstructs whose lengths share a tile bucket: the
    second compiles nothing, and each is exactly one device program."""
    codec = RSCodec(backend="jax")
    rng = np.random.RandomState(n_first)
    seen = []
    for n in (n_first, n_second):
        shards = RSCodec(backend="numpy").encode_all(
            rng.randint(0, 256, size=(10, n), dtype=np.uint8))
        surviving = {i: shards[i] for i in range(14) if i != 3}
        before = device_programs()
        got = codec.reconstruct(surviving, targets=[3])
        assert np.array_equal(got[3], shards[3])
        assert device_programs() - before == 1
        seen.append(compile_requests())
    assert seen[1] == seen[0]


@pytest.mark.parametrize("lost", [3, 11], ids=["lost-data", "lost-parity"])
@pytest.mark.parametrize("n", door_widths(rs_kernel.TILE))
def test_door_host_bytes_match_the_oracle(n, lost):
    assert door_widths(8192) == (1, 8191, 8192, 8193, 27720, 65576, 73728)
    check_door_against_oracle(n, lost)


def test_door_lengths_of_one_bucket_share_one_program():
    check_one_bucket_one_program(8192 + 40, 2 * 8192 - 7)


# --- the ladder: a closed set of kernel widths up to one small block ----------
# Host bytes reach the kernel at a rung of `rs_kernel.LADDER_TILES`, so a
# degraded read of any length up to a block compiles nothing beyond the rungs.

RUNGS = rs_kernel.LADDER_TILES
LOST = 6
# data shards without the lost one, and the first parity shard: ten survivors
SURVIVORS = tuple(i for i in range(11) if i != LOST)
LADDER_MATRIX = gf256.decode_matrix(10, 4, SURVIVORS, (LOST,)).tobytes()


def test_ladder_is_small_closed_and_ends_at_one_small_block():
    from seaweedfs_tpu.storage.erasure_coding.geometry import SMALL_BLOCK_SIZE

    assert list(RUNGS) == sorted(set(RUNGS)) and len(RUNGS) <= 20
    assert RUNGS[-1] * rs_kernel.TILE == SMALL_BLOCK_SIZE
    assert RUNGS[:9] == tuple(range(1, 10))  # every width a 64 KiB needle makes
    for lo, hi in zip(RUNGS[8:], RUNGS[9:]):  # a step up wastes under a third
        assert (hi - lo) * 3 <= hi


@pytest.mark.parametrize("n", [1, 40, 8191, 8192, 8193, 27720, 65536, 65576,
                               73727, 73728])
def test_ladder_maps_a_64k_needles_widths_as_before(n):
    assert rs_kernel.ladder_width(n, 8192) == n + (-n) % 8192


@pytest.mark.parametrize("n,want", [
    (73729, 98304), (98304, 98304), (98305, 131072), (131073, 196608),
    (700000, 786432), (786433, 1048576), (1048576, 1048576),
    # beyond one small block (rows of large blocks): tile multiples, as before
    (1048577, 1048576 + 8192), (4 * 1048576 + 5, 4 * 1048576 + 8192),
])
def test_ladder_above_a_64k_needle_and_beyond_a_block(n, want):
    assert rs_kernel.ladder_width(n, 8192) == want


def kernel_shapes() -> int:
    return device.report()["kernel_shapes"]


def ladder_reconstruct(codec: RSCodec, n: int, seed: int) -> None:
    """Shard LOST of n seeded columns rebuilt through the codec's door from
    nine data shards and one parity row of the numpy oracle, and held to the
    bytes that were encoded."""
    rng = np.random.Generator(np.random.SFC64([seed, n]))
    data = rng.integers(0, 256, size=(10, n), dtype=np.uint8)
    parity = gf256.gf_matmul_bytes(gf256.parity_rows(10, 4)[:1], data)
    shards = {i: (data[i] if i < 10 else parity[0]) for i in SURVIVORS}
    got = codec.reconstruct(shards, targets=[LOST])[LOST]
    assert got.shape == (n,) and np.array_equal(got, data[LOST])


def warm_ladder(tile: int) -> dict:
    """One reconstruct at every rung; what the checks below start from."""
    codec = RSCodec(backend="jax")
    shapes = kernel_shapes() if device.started() else 0
    for t in RUNGS:
        ladder_reconstruct(codec, t * tile, seed=1)
    return {"codec": codec, "tile": tile, "shapes_before": shapes}


def check_ladder_widths(ladder: dict, widths) -> None:
    """Each width goes to the kernel at a rung at or above it, with a zero
    tail of at most a third above nine tiles and the tile multiple below; it
    is one device program, compiles nothing and builds no new shape."""
    codec, tile = ladder["codec"], ladder["tile"]
    compiles, shapes = compile_requests(), kernel_shapes()
    # the rungs' programs and no other, however many the process had before:
    # one a (rows, cols) and width, whatever the matrix
    assert shapes - ladder["shapes_before"] <= len(RUNGS) <= 20
    assert all((1, 10, t * tile, 0) in device._kernel_shapes for t in RUNGS)
    for n in widths:
        width = rs_kernel.ladder_width(n, tile)
        assert width >= n and width // tile in RUNGS and width % tile == 0
        if n <= 9 * tile:
            assert width == n + (-n) % tile
        else:
            assert (width - n) * 3 <= width
        before = device_programs()
        ladder_reconstruct(codec, n, seed=2)
        assert device_programs() - before == 1
    assert compile_requests() == compiles and kernel_shapes() == shapes


def rung_neighbours(rung: int, tile: int) -> list[int]:
    w = rung * tile
    return [w - 1, w] + ([w + 1] if rung != RUNGS[-1] else [])


def sweep_widths(seed: int, tile: int, count: int = 50) -> list[int]:
    rng = np.random.Generator(np.random.SFC64([28, seed]))
    return [int(n) for n in rng.integers(1, RUNGS[-1] * tile + 1, size=count)]


@pytest.fixture(scope="module")
def xla_ladder():
    """The XLA form (the CPU's) at the kernel's own tile: rungs of 8 KiB to
    1 MiB."""
    return warm_ladder(rs_kernel.TILE)


@pytest.mark.parametrize("rung", RUNGS)
def test_ladder_rung_neighbours_share_the_rungs_programs(xla_ladder, rung):
    check_ladder_widths(xla_ladder, rung_neighbours(rung, xla_ladder["tile"]))


@pytest.mark.parametrize("seed", range(4))
def test_ladder_sweep_of_widths_up_to_a_block_compiles_nothing(xla_ladder, seed):
    check_ladder_widths(xla_ladder, sweep_widths(seed, xla_ladder["tile"]))


def check_direct_host_array_goes_to_a_rung(tiles: int, tile: int) -> None:
    """A host array handed to the door itself, not through the codec,
    reaches it at a rung too: the zero tail on the host, the kernel alone on
    the device, the tail dropped on the host, and the oracle's bytes."""
    n = tiles * tile
    width = rs_kernel.ladder_width(n, tile)
    assert width > n and width // tile in RUNGS
    matrix = np.frombuffer(LADDER_MATRIX, dtype=np.uint8).reshape(1, 10)
    rng = np.random.Generator(np.random.SFC64([28, tiles]))
    data = rng.integers(0, 256, size=(10, n), dtype=np.uint8)
    before = set(device._kernel_shapes)
    out, programs = rs_kernel._enqueue(matrix, data)
    assert programs == 1
    assert set(device._kernel_shapes) - before <= {(1, 10, width, 0)}
    assert (1, 10, width, 0) in device._kernel_shapes
    assert out.dtype == np.uint32 and out.size == width // 4
    got = rs_kernel._JaxHandle(out, n).result()
    assert got.shape == (1, n) and got.dtype == np.uint8
    assert np.array_equal(got, gf256.gf_matmul_bytes(matrix, data))


OFF_THE_LADDER = [10, 11, 13, 100]  # tile multiples that are no rung


@pytest.mark.parametrize("tiles", OFF_THE_LADDER)
def test_host_array_off_the_ladder_goes_to_a_rung(tiles):
    check_direct_host_array_goes_to_a_rung(tiles, rs_kernel.TILE)
