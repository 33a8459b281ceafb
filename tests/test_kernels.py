"""Pallas kernel validation (interpret=True on CPU) vs pure-jnp ref oracles.

The raw kernels take ``interpret`` as a required keyword; these tests pass
``interpret=True`` themselves, the ops pick it per platform.

Per kernel: sweep shapes (aligned, unaligned, tiny, large) and value ranges,
assert_allclose against ref.py, plus hypothesis property tests on invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep: see requirements-dev.txt
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.fedavg.fedavg import fedavg_reduce
from repro.kernels.fedavg.ref import ATOL, RTOL, fedavg_ref
from repro.kernels.q8_block.q8_block import (
    BLOCK,
    dequantize_q8,
    div_rn,
    quantize_q8,
)
from repro.kernels.q8_block.ref import dequantize_q8_ref, quantize_q8_ref
from repro.kernels.quantize_f16.ops import (
    f16_payload_to_params,
    params_to_f16_payload,
)
from repro.kernels.quantize_f16.quantize_f16 import dequantize_f16, quantize_f16
from repro.kernels.quantize_f16.ref import dequantize_f16_ref, quantize_f16_ref

SIZES = [1, 7, 128, 1024, 1025, 44_426, 262_144]  # incl. LeNet-5 param count


# --- quantize_f16 -------------------------------------------------------------

@pytest.mark.parametrize("n", SIZES)
def test_quantize_f16_matches_ref(n):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_normal(n) * 100, jnp.float32)
    out = quantize_f16(x, interpret=True)
    ref = quantize_f16_ref(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("n", [128, 4096])
def test_dequantize_f16_matches_ref(n):
    rng = np.random.default_rng(n)
    bits = jnp.asarray(rng.integers(0, 2**16, n), jnp.uint16)
    out = dequantize_f16(bits, interpret=True)
    ref = dequantize_f16_ref(bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@given(st.lists(st.floats(width=16, allow_nan=False), min_size=1, max_size=300))
@settings(max_examples=30, deadline=None)
def test_f16_roundtrip_exact_for_representable(values):
    x = jnp.asarray(np.array(values, np.float16).astype(np.float32))
    back = dequantize_f16(quantize_f16(x, interpret=True), interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_f16_payload_matches_cbor_typed_array():
    """Kernel payload bytes == numpy astype('<f2') bytes (CBOR tag 84)."""
    rng = np.random.default_rng(0)
    flat = jnp.asarray(rng.standard_normal(5000), jnp.float32)
    payload = params_to_f16_payload(flat)
    expected = np.asarray(flat).astype("<f2").tobytes()
    assert payload == expected
    back = f16_payload_to_params(payload)
    np.testing.assert_array_equal(back, np.asarray(flat).astype(np.float16)
                                  .astype(np.float32))


_F16_EDGE_BITS = {
    # f32 bit patterns at every branch of the integer f32 -> f16 rounding
    "signed_zeros": [0x00000000, 0x80000000],
    "f32_subnormals": [0x00000001, 0x007FFFFF, 0x80400000],
    "below_half_min_subnormal": [0x32FFFFFF, 0x33000000, 0xB3000000],
    "f16_subnormals": [0x33000001, 0x33800000, 0x337FFFFF, 0x38000000,
                       0x387FC000, 0x387FE000, 0x387FF000, 0x387FFFFF],
    "subnormal_ties": [0x33C00000, 0x34200000, 0x34A00000, 0x35500000],
    "normal_ties": [0x3F801000, 0x3F803000, 0x3F801001, 0x477FEFFF,
                    0x477FF000],
    "overflow": [0x47800000, 0x7F7FFFFF, 0xC7800000],
    "inf": [0x7F800000, 0xFF800000],
    "nan_payloads": [0x7FC00000, 0xFFC00001, 0x7F800001, 0x7F802000,
                     0x7FBFFFFF, 0xFFFFFFFF],
}


@pytest.mark.parametrize("case", sorted(_F16_EDGE_BITS))
def test_quantize_f16_edge_bits_match_numpy(case):
    """Subnormals, ties, overflow, ±inf and NaN payloads: the kernel's
    integer rounding is bit-identical to numpy's ``astype("<f2")``."""
    bits = np.array(_F16_EDGE_BITS[case], np.uint32)
    x = bits.view(np.float32)
    with np.errstate(over="ignore"):
        expected = x.astype("<f2").view(np.uint16)
    out = np.asarray(quantize_f16(jnp.asarray(x), interpret=True))
    assert [hex(v) for v in out] == [hex(v) for v in expected]


def test_dequantize_f16_all_bit_patterns_match_numpy():
    """Every one of the 65,536 half patterns widens exactly as numpy does,
    NaN payloads included."""
    bits = np.arange(1 << 16, dtype=np.uint32).astype(np.uint16)
    expected = bits.view(np.float16).astype(np.float32).view(np.uint32)
    out = np.asarray(dequantize_f16(jnp.asarray(bits), interpret=True))
    np.testing.assert_array_equal(out.view(np.uint32), expected)


# --- q8_block -----------------------------------------------------------------

@pytest.mark.parametrize("nblocks", [1, 2, 127, 128, 129, 1000])
@pytest.mark.parametrize("scale", [1e-3, 1.0, 1e4])
def test_q8_matches_ref(nblocks, scale):
    rng = np.random.default_rng(nblocks)
    x = jnp.asarray(rng.standard_normal((nblocks, BLOCK)) * scale, jnp.float32)
    q, s = quantize_q8(x, interpret=True)
    q_ref, s_ref = quantize_q8_ref(x)
    # f32 associativity (reciprocal-multiply vs divide) allows 1-2 ULP drift
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6)
    diff = np.abs(np.asarray(q).astype(int) - np.asarray(q_ref).astype(int))
    assert diff.max() <= 1 and (diff != 0).mean() < 1e-3
    deq = dequantize_q8(q, s, interpret=True)
    deq_ref = dequantize_q8_ref(q_ref, s_ref)
    np.testing.assert_allclose(np.asarray(deq), np.asarray(deq_ref),
                               rtol=1e-6, atol=float(scale) * 1e-2)


def test_div_rn_is_correctly_rounded():
    """The kernel's integer long division equals IEEE f32 division, at
    random operands and at exact and near rounding ties."""
    rng = np.random.default_rng(0)
    n = 1 << 14
    a = (rng.random(n) * 2.0 ** rng.integers(-40, 40, n)).astype(np.float32)
    b = (rng.random(n) * 2.0 ** rng.integers(-40, 40, n)).astype(np.float32)
    b = np.maximum(b, np.float32(1e-30))
    tb = (rng.integers(1, 1 << 10, n) * 2.0 ** rng.integers(-20, 20, n)
          ).astype(np.float32)
    tie = ((rng.integers(0, 128, n) + 0.5) * tb.astype(np.float64)
           ).astype(np.float32)
    cases = [(a, b), (tie, tb), (np.nextafter(tie, np.float32(np.inf)), tb),
             (np.nextafter(tie, np.float32(0)), tb)]
    for num, den in cases:
        got = np.asarray(jax.jit(div_rn)(jnp.asarray(num), jnp.asarray(den)))
        want = num / den
        normal = want >= np.float32(2.0 ** -126)
        np.testing.assert_array_equal(got[normal].view(np.uint32),
                                      want[normal].view(np.uint32))


@pytest.mark.parametrize("nblocks", [1, 129, 1000])
@pytest.mark.parametrize("scale", [1e-30, 1e-3, 1.0, 1e4, 1e30])
def test_q8_bit_identical_to_host_codec(nblocks, scale):
    """Values and scale bytes equal ``params_codec.quantize_q8``'s, tie
    blocks included (what makes kernel and numpy chunk payloads equal)."""
    from repro.core.params_codec import quantize_q8 as host_q8

    rng = np.random.default_rng(nblocks)
    x = (rng.standard_normal((nblocks, BLOCK)) * scale).astype(np.float32)
    ties = np.concatenate([[127.0], np.arange(-127, 127) + 0.5, [-3.5]])
    x[0] = ties * 2.0 ** np.round(np.log2(scale))
    q, s = quantize_q8(jnp.asarray(x), interpret=True)
    q_h, s_h, _ = host_q8(x.reshape(-1), BLOCK)
    np.testing.assert_array_equal(np.asarray(q).reshape(-1), q_h)
    np.testing.assert_array_equal(np.asarray(s).view(np.uint32),
                                  s_h.view(np.uint32))


def test_q8_zero_block_safe():
    x = jnp.zeros((4, BLOCK), jnp.float32)
    q, s = quantize_q8(x, interpret=True)
    assert not np.isnan(np.asarray(s)).any()
    np.testing.assert_array_equal(np.asarray(q), 0)


@given(st.integers(1, 50), st.integers(0, 10))
@settings(max_examples=20, deadline=None)
def test_q8_error_bound_property(nblocks, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((nblocks, BLOCK)), jnp.float32)
    q, s = quantize_q8(x, interpret=True)
    err = np.abs(np.asarray(dequantize_q8(q, s, interpret=True))
                 - np.asarray(x))
    bound = np.abs(np.asarray(x)).max(1) / 127.0 * 0.5 + 1e-6
    assert (err <= bound[:, None]).all()


# --- fedavg -------------------------------------------------------------------

@pytest.mark.parametrize("k,n", [(1, 100), (3, 2048), (16, 44_426), (64, 4096)])
def test_fedavg_matches_ref(k, n):
    rng = np.random.default_rng(k * n)
    updates = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    weights = jnp.asarray(rng.integers(1, 500, k), jnp.float32)
    out = fedavg_reduce(updates, weights, interpret=True)
    ref = fedavg_ref(updates, weights)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


def test_fedavg_identity_single_client():
    u = jnp.asarray(np.random.default_rng(0).standard_normal((1, 333)),
                    jnp.float32)
    out = fedavg_reduce(u, jnp.asarray([17.0]), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(u[0]), rtol=1e-6)


@given(st.integers(2, 8), st.integers(1, 500))
@settings(max_examples=20, deadline=None)
def test_fedavg_convexity_property(k, n):
    """Output is inside the per-coordinate envelope of the inputs."""
    rng = np.random.default_rng(k + n)
    updates = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    weights = jnp.asarray(rng.integers(1, 100, k), jnp.float32)
    out = np.asarray(fedavg_reduce(updates, weights, interpret=True))
    u = np.asarray(updates)
    assert (out <= u.max(0) + 1e-5).all() and (out >= u.min(0) - 1e-5).all()


def test_fedavg_agrees_with_fl_aggregation():
    """Kernel result == the FL runtime's numpy fedavg."""
    from repro.fl.aggregation import fedavg as np_fedavg
    rng = np.random.default_rng(5)
    updates = rng.standard_normal((5, 1000)).astype(np.float32)
    sizes = rng.integers(10, 100, 5)
    a = np_fedavg(list(updates), list(sizes))
    b = np.asarray(fedavg_reduce(jnp.asarray(updates),
                                 jnp.asarray(sizes, jnp.float32),
                                 interpret=True))
    np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)
