"""Fast-wire tests (docs/WIRE.md): the framed codec's structural
roundtrips and integrity checks, quantization + error feedback, the
coalesced scatter, and mixed-version (framed vs pickle-only) peers
completing real EASGD exchanges over sockets."""

import os
import threading
import time

import numpy as np
import pytest

from mpit_tpu.parallel.pclient import PClient
from mpit_tpu.parallel.pserver import (
    TAG_PUSH_EASGD,
    PServer,
    spawn_server_thread,
)
from mpit_tpu.transport import Broker, SocketTransport
from mpit_tpu.transport import wire
from mpit_tpu.transport.wire import (
    WIRE_FORMAT_VERSION,
    QuantArray,
    WireDecodeError,
    dequantize,
    quantize,
)

DIM = 16


def _roundtrip(payload, src=3, tag=2):
    """encode → (simulated wire) → decode, returning (src, tag, payload).
    Joins the zero-copy buffer list the way the socket writes it."""
    bufs = wire.encode_frame(src, tag, payload, version=WIRE_FORMAT_VERSION)
    assert bufs is not None
    head = bytes(bufs[0])
    body = b"".join(bytes(b) for b in bufs[1:])
    version, flags, hlen, hcrc = wire.split_preamble(
        head[: wire.PREAMBLE_SIZE]
    )
    assert version == WIRE_FORMAT_VERSION
    assert hlen == len(head) - wire.PREAMBLE_SIZE
    return wire.decode_frame(flags, hcrc, head[wire.PREAMBLE_SIZE:], body)


class TestCodec:
    def test_structural_roundtrip(self):
        payload = (
            None, True, False, 0, -17, 3.25, "τ-steps", b"\x00\xff",
            ["a", (1, 2.0, None)], [],
        )
        src, tag, out = _roundtrip(payload, src=5, tag=9)
        assert (src, tag) == (5, 9)
        assert out == payload

    def test_epoch_int_wider_than_u64(self):
        # client epochs come from os.urandom(8) and CAN exceed a signed
        # 64-bit slot; arbitrary-width magnitudes are part of the format
        for v in (2 ** 63, 2 ** 80 + 13, -(2 ** 70), 2 ** 64 - 1):
            assert _roundtrip((v, 1, 0, None))[2][0] == v

    def test_ndarray_roundtrip_and_views(self):
        arr = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
        bufs = wire.encode_frame(
            0, 2, arr, version=WIRE_FORMAT_VERSION
        )
        # send side is zero-copy: the body buffer aliases the input array
        assert isinstance(bufs[1], memoryview)
        assert bufs[1].obj is arr.data.obj or np.shares_memory(
            np.frombuffer(bufs[1], dtype=np.float32).reshape(arr.shape),
            arr,
        )
        _, _, out = _roundtrip(arr)
        np.testing.assert_array_equal(out, arr)
        assert out.dtype == arr.dtype and out.shape == arr.shape
        # recv side is zero-copy: the decoded array is a view into the
        # body buffer, not a fresh allocation
        assert not out.flags.owndata

    def test_every_registered_dtype_roundtrips(self):
        for dtype in (
            np.float32, np.float64, np.float16, np.int64, np.int32,
            np.int16, np.int8, np.uint8, np.uint16, np.uint32,
            np.uint64, np.bool_,
        ):
            arr = np.zeros(5, dtype=dtype)
            arr[1] = 1
            _, _, out = _roundtrip(arr)
            assert out.dtype == np.dtype(dtype)
            np.testing.assert_array_equal(out, arr)

    def test_unencodable_returns_none_for_pickle_fallback(self):
        from mpit_tpu.transport.chaos import CorruptedPayload

        for payload in (
            object(), {"a": 1}, np.float32(1.5), CorruptedPayload(),
            (1, 2, {3}),
        ):
            assert wire.encode_frame(
                0, 1, payload, version=WIRE_FORMAT_VERSION
            ) is None

    def test_header_crc_flip_raises(self):
        bufs = wire.encode_frame(
            1, 2, (1, 2, np.ones(4, np.float32)),
            version=WIRE_FORMAT_VERSION,
        )
        head = bytearray(bytes(bufs[0]))
        body = b"".join(bytes(b) for b in bufs[1:])
        head[wire.PREAMBLE_SIZE] ^= 0x40  # flip a structural header bit
        _, flags, _, hcrc = wire.split_preamble(
            bytes(head[: wire.PREAMBLE_SIZE])
        )
        with pytest.raises(WireDecodeError, match="CRC"):
            wire.decode_frame(
                flags, hcrc, bytes(head[wire.PREAMBLE_SIZE:]), body
            )

    def test_body_length_mismatch_carries_src_tag(self):
        arr = np.ones(8, np.float32)
        bufs = wire.encode_frame(
            7, 4, (1, 2, arr), version=WIRE_FORMAT_VERSION
        )
        head = bytes(bufs[0])
        body = b"".join(bytes(b) for b in bufs[1:])
        _, flags, _, hcrc = wire.split_preamble(head[: wire.PREAMBLE_SIZE])
        with pytest.raises(WireDecodeError) as ei:
            wire.decode_frame(
                flags, hcrc, head[wire.PREAMBLE_SIZE:], body[:-4]
            )
        # src/tag decoded before the body check: the transport can still
        # route a corruption marker to the right (src, tag) stream
        assert ei.value.src == 7 and ei.value.tag == 4
        with pytest.raises(WireDecodeError, match="mismatch"):
            wire.decode_frame(
                flags, hcrc, head[wire.PREAMBLE_SIZE:], body + b"xx"
            )

    def test_future_version_rejected(self):
        bufs = wire.encode_frame(
            0, 1, None, version=WIRE_FORMAT_VERSION + 1
        )
        with pytest.raises(WireDecodeError, match="newer"):
            wire.split_preamble(bytes(bufs[0])[: wire.PREAMBLE_SIZE])
        with pytest.raises(ValueError, match="out of range"):
            wire.encode_frame(0, 1, None, version=300)

    def test_no_magic_collision_with_pickle(self):
        # per-frame dispatch depends on it: a protocol>=2 pickle always
        # starts 0x80, a framed body always starts b"MW"
        import pickle

        assert wire.MAGIC[0:1] != pickle.dumps(None, protocol=5)[0:1]
        assert wire.MAGIC == b"MW"

    def test_hello_roundtrip_and_rejects_garbage(self):
        assert wire.decode_hello(wire.encode_hello()) == (
            WIRE_FORMAT_VERSION
        )
        assert wire.decode_hello(b"") is None
        assert wire.decode_hello(b"\x80\x05x") is None
        assert wire.decode_hello(b"MWX\x01") is None

    def test_frame_nbytes_counts_whole_body(self):
        arr = np.ones(10, np.float32)
        bufs = wire.encode_frame(
            0, 2, arr, version=WIRE_FORMAT_VERSION
        )
        joined = bytes(bufs[0]) + b"".join(bytes(b) for b in bufs[1:])
        assert wire.frame_nbytes(bufs) == len(joined)


class TestQuantization:
    def test_bf16_roundtrip_precision(self):
        rng = np.random.default_rng(0)
        a = rng.standard_normal(4096).astype(np.float32) * 100
        out = dequantize(quantize(a, "bf16"))
        # bf16 keeps 8 mantissa bits: relative error < 2^-8 after RNE
        nz = np.abs(a) > 0
        assert np.max(np.abs(out[nz] - a[nz]) / np.abs(a[nz])) < 2 ** -8

    def test_int8_symmetric_absmax(self):
        a = np.array([-4.0, -1.0, 0.0, 2.0, 4.0], np.float32)
        q = quantize(a, "int8")
        assert q.mode == "int8" and q.data.dtype == np.int8
        assert q.scale == pytest.approx(4.0 / 127.0)
        out = dequantize(q)
        assert np.max(np.abs(out - a)) <= q.scale / 2 + 1e-7
        # all-zero chunk must not divide by zero
        z = quantize(np.zeros(3, np.float32), "int8")
        np.testing.assert_array_equal(dequantize(z), np.zeros(3))

    def test_quant_array_over_the_wire(self):
        a = np.linspace(-1, 1, 64, dtype=np.float32)
        q = quantize(a, "int8")
        _, _, out = _roundtrip((123, 4, 0, q))
        got = out[3]
        assert isinstance(got, QuantArray)
        assert got.mode == "int8" and got.scale == pytest.approx(q.scale)
        np.testing.assert_allclose(
            dequantize(got), a, atol=q.scale / 2 + 1e-7
        )

    def test_error_feedback_cancels_quantizer_bias(self):
        # EF contract (docs/WIRE.md): residual carried into the next
        # push makes the MEAN of dequantized pushes converge to the true
        # vector far beyond one push's quantization error
        rng = np.random.default_rng(3)
        target = rng.standard_normal(256).astype(np.float32)
        res = np.zeros_like(target)
        acc = np.zeros_like(target)
        n = 50
        for _ in range(n):
            comp = target + res
            q = quantize(comp, "int8")
            deq = dequantize(q)
            res = comp - deq
            acc += deq
        one_shot = np.mean(
            np.abs(dequantize(quantize(target, "int8")) - target)
        )
        ef_err = np.mean(np.abs(acc / n - target))
        assert ef_err < one_shot / 10

    def test_env_readers_validate(self, monkeypatch):
        assert wire.wire_format_from_env({}) == "framed"
        assert wire.quant_mode_from_env({}) == "off"
        assert wire.negotiate_enabled_from_env({}) is True
        assert wire.negotiate_enabled_from_env(
            {"MPIT_WIRE_NEGOTIATE": "0"}
        ) is False
        assert wire.negotiate_timeout_from_env(
            {"MPIT_WIRE_NEGOTIATE_TIMEOUT_S": "0.25"}
        ) == 0.25
        with pytest.raises(ValueError, match="MPIT_WIRE_FORMAT"):
            wire.wire_format_from_env({"MPIT_WIRE_FORMAT": "msgpack"})
        with pytest.raises(ValueError, match="MPIT_WIRE_QUANT"):
            wire.quant_mode_from_env({"MPIT_WIRE_QUANT": "fp4"})
        with pytest.raises(ValueError, match="quant"):
            PClient(Broker(2).transports()[1], [0], DIM, quant="fp4")


class TestQuantHardening:
    """The hardened-kernel contract (docs/ANALYSIS.md, RT104): on the
    int8 faces, non-finite inputs and degenerate blocks must produce a
    finite scale and finite codes — a NaN gradient element may poison
    ITS lane's code (pinned to 0) but never the block scale, and an
    all-zero or all-NaN block quantizes to zeros at scale 1 instead of
    dividing by zero. bf16 represents NaN and passes it through bit-true
    (RT104 reports it at the boundary instead of the kernel hiding it)."""

    def test_all_nan_block_pins_scale_and_codes(self):
        q = quantize(np.full(6, np.nan, np.float32), "int8")
        assert q.scale == 1.0
        np.testing.assert_array_equal(q.data, np.zeros(6, np.int8))
        np.testing.assert_array_equal(dequantize(q), np.zeros(6))

    def test_inf_sets_scale_from_finite_values_nan_lane_zeroed(self):
        a = np.array([1.0, np.inf, -np.inf, np.nan], np.float32)
        q = quantize(a, "int8")
        # absmax over the FINITE values only: 1.0 -> scale 1/127
        assert q.scale == pytest.approx(1.0 / 127.0)
        # inf lanes saturate, the nan lane pins to 0
        np.testing.assert_array_equal(
            q.data, np.array([127, 127, -127, 0], np.int8)
        )
        out = dequantize(q)
        assert np.isfinite(out).all()

    def test_empty_chunk_roundtrips_on_both_layouts(self):
        from mpit_tpu import quant as qk

        q = quantize(np.zeros(0, np.float32), "int8")
        assert q.scale == 1.0 and dequantize(q).shape == (0,)
        codes, scales = qk.quantize_rows(
            np.zeros((0, 4), np.float32), "int8"
        )
        assert codes.shape == (0, 4) and scales.shape == (0, 1)
        assert qk.dequantize_rows(codes, scales, "int8").shape == (0, 4)

    def test_rows_face_matches_per_row_scalar_on_poisoned_input(self):
        from mpit_tpu import quant as qk

        rng = np.random.default_rng(11)
        a = rng.standard_normal((5, 32)).astype(np.float32)
        a[0, 3] = np.nan
        a[1, :] = np.nan  # all-NaN row
        a[2, 7] = np.inf
        a[3, :] = 0.0  # all-zero row
        codes, scales = qk.quantize_rows(a, "int8")
        for j in range(a.shape[0]):
            host = quantize(a[j], "int8")
            np.testing.assert_array_equal(codes[j], host.data)
            assert np.float32(host.scale).tobytes() == (
                scales[j].astype(np.float32).tobytes()
            )
        np.testing.assert_array_equal(
            qk.dequantize_rows(codes, scales, "int8"),
            np.stack([dequantize(quantize(a[j], "int8"))
                      for j in range(a.shape[0])]),
        )

    def test_jnp_faces_match_host_on_poisoned_input(self):
        from mpit_tpu import quant as qk

        a = np.array(
            [[1.0, np.inf, np.nan, -2.0],
             [np.nan, np.nan, np.nan, np.nan],
             [0.0, 0.0, 0.0, 0.0]],
            np.float32,
        )
        codes, scale = qk.quantize_jnp(a.ravel(), "int8")
        host = quantize(a.ravel(), "int8")
        np.testing.assert_array_equal(np.asarray(codes), host.data)
        assert np.isfinite(
            np.asarray(qk.dequantize_jnp(codes, scale, "int8"))
        ).all()
        codes, scales = qk.quantize_rows_jnp(a, "int8")
        h_codes, h_scales = qk.quantize_rows(a, "int8")
        np.testing.assert_array_equal(np.asarray(codes), h_codes)
        np.testing.assert_array_equal(
            np.asarray(scales, np.float32), h_scales.astype(np.float32)
        )

    def test_top_of_the_float32_range_on_both_faces(self):
        """int8: a finite input never reconstructs to inf (FLT_MAX / 127
        rounds up, and 127 times that overflows: the scale is held one
        ulp under it), the same scale to the bit on every face. bf16:
        from half a step above bfloat16's largest finite value on,
        round-to-nearest-even gives infinity, as ``astype(jnp.bfloat16)``
        does; under that, that value."""
        import jax.numpy as jnp

        from mpit_tpu import quant as qk

        top = np.finfo(np.float32).max
        a = np.array(
            [[top, -1.0, 0.0, 3.0e38],
             [3.39617752923046e38, -top, 1.0e38, np.inf],
             [qk.BF16_MAX, -qk.BF16_MAX, 3.3895315920756315e38, np.nan]],
            np.float32,
        )
        h_codes, h_scales = qk.quantize_rows(a, "int8")
        d_codes, d_scales = qk.quantize_rows_jnp(a, "int8")
        np.testing.assert_array_equal(np.asarray(d_codes), h_codes)
        assert np.asarray(d_scales, np.float32).tobytes() == (
            h_scales.tobytes())
        for j, row in enumerate(a):
            host = quantize(row, "int8")
            codes, scale = qk.quantize_jnp(row, "int8")
            assert np.float32(host.scale).tobytes() == (
                np.asarray(scale, np.float32).tobytes()
                ) == h_scales[j].tobytes()
            for out in (dequantize(host),
                        np.asarray(qk.dequantize_jnp(codes, scale, "int8")),
                        qk.dequantize_rows(h_codes, h_scales, "int8")[j]):
                assert np.isfinite(out).all(), (row, out)
                assert abs(out[0] - row[0]) <= 0.51 * host.scale
        assert np.isfinite(np.float32(127) * qk._INT8_SCALE_MAX)

        want = np.asarray(
            jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32))
        host = dequantize(quantize(a, "bf16"))
        np.testing.assert_array_equal(host, want)
        np.testing.assert_array_equal(
            np.asarray(qk.dequantize_jnp(
                qk.quantize_jnp(a, "bf16")[0], None, "bf16")), want)
        assert np.isposinf(host[0, 0]) and np.isposinf(host[1, 0])
        assert np.isneginf(host[1, 1]) and np.isfinite(host[0, 3])
        assert host[2, 0] == qk.BF16_MAX and host[2, 1] == -qk.BF16_MAX
        assert host[2, 2] == qk.BF16_MAX

    def test_bf16_preserves_nan_and_rt104_reports_it(self):
        # bf16 REPRESENTS NaN, so the kernel passes it through bit-true
        # (no silent zeroing that would hide the bug) — detection is the
        # runtime sanitizer's job, at the quantize boundary
        from mpit_tpu.analysis import runtime as rt

        a = np.array([1.5, np.nan, -2.25], np.float32)
        out = dequantize(quantize(a, "bf16"))
        assert np.isnan(out[1])
        # whatever its payload: the rounding's carry must not take a NaN
        # through the sign bit to a zero, nor drop a low-half payload
        # and leave an infinity, on either face
        from mpit_tpu import quant as qk

        nans = np.array(
            [0x7FFFF8EC, 0xFFFFFFFF, 0x7F800001, 0xFF800001], np.uint32
        ).view(np.float32)
        host = quantize(nans, "bf16").data
        assert np.isnan(dequantize(quantize(nans, "bf16"))).all()
        np.testing.assert_array_equal(
            np.asarray(qk.quantize_jnp(nans, "bf16")[0]), host)
        assert out[0] == pytest.approx(1.5) and out[2] == pytest.approx(-2.25)
        with rt.checking(numerics=True) as ck:
            quantize(a, "bf16")
        assert [f.rule for f in ck.findings] == ["RT104"]


class TestHostDeviceKernelEquivalence:
    """The factored kernels (mpit_tpu.quant) must agree BIT-FOR-BIT
    between the numpy (wire) and jnp (collective) paths: the error-
    feedback residual treats deq(quant(x)) as one deterministic
    function, so any host/device disagreement becomes exactly that much
    bias in the gradient average."""

    def _vectors(self):
        rng = np.random.default_rng(7)
        return np.concatenate([
            rng.standard_normal(1024).astype(np.float32) * 1e3,
            # edge cases: signed zero, exact powers of two (bf16 RNE
            # halfway carries), denormal-ish tiny, large
            np.array([0.0, -0.0, 1.0, -1.0, 2.0 ** -120, 6.5e4,
                      0.5, -3.0], np.float32),
        ])

    def test_bf16_rne_bits_match(self):
        from mpit_tpu import quant as qk

        a = self._vectors()
        host = quantize(a, "bf16")
        codes, scale = qk.quantize_jnp(a, "bf16")
        np.testing.assert_array_equal(np.asarray(codes), host.data)
        np.testing.assert_array_equal(
            np.asarray(qk.dequantize_jnp(codes, scale, "bf16")),
            dequantize(host),
        )

    def test_int8_absmax_bits_match(self):
        from mpit_tpu import quant as qk

        a = self._vectors()
        host = quantize(a, "int8")
        codes, scale = qk.quantize_jnp(a, "int8")
        np.testing.assert_array_equal(np.asarray(codes), host.data)
        # the scale itself is bit-equal, not approx: both paths divide
        # in f32 (a float64 host division would double-round)
        assert np.float32(host.scale).tobytes() == (
            np.asarray(scale, np.float32).tobytes()
        )
        np.testing.assert_array_equal(
            np.asarray(qk.dequantize_jnp(codes, scale, "int8")),
            dequantize(host),
        )
        # all-zero block: scale pinned to 1 on both paths
        z_codes, z_scale = qk.quantize_jnp(
            np.zeros(5, np.float32), "int8"
        )
        assert float(z_scale) == quantize(
            np.zeros(5, np.float32), "int8"
        ).scale == 1.0

    def test_blockwise_rows_equal_per_row_host_quantize(self):
        from mpit_tpu import quant as qk

        rng = np.random.default_rng(9)
        a = rng.standard_normal((4, 64)).astype(np.float32) * 10
        a[2] = 0.0  # one all-zero block
        codes, scales = qk.quantize_rows_jnp(a, "int8")
        for j in range(a.shape[0]):
            host = quantize(a[j], "int8")
            np.testing.assert_array_equal(np.asarray(codes)[j], host.data)
            assert np.float32(host.scale).tobytes() == (
                np.asarray(scales, np.float32)[j].tobytes()
            )
        np.testing.assert_array_equal(
            np.asarray(qk.dequantize_rows_jnp(codes, scales, "int8")),
            np.stack([dequantize(quantize(a[j], "int8"))
                      for j in range(a.shape[0])]),
        )


class TestCoalescedScatter:
    def _world(self, center=0.0, **server_kw):
        tps = Broker(2).transports()
        server = PServer(
            tps[0], np.full(DIM, center, np.float32), num_clients=1,
            **server_kw,
        )
        thread = spawn_server_thread(server)
        return tps, server, thread

    def test_repeated_rank_coalesces_to_one_message(self):
        tps, server, thread = self._world()
        # one server owning two adjacent chunks: the classic sharded
        # layout collapsed onto one rank — chunks must merge
        client = PClient(tps[1], [0, 0], DIM, timeout=5)
        assert client.ranks == [0]
        assert client.rank_bounds == [(0, DIM)]
        client.push_easgd(np.ones(DIM, np.float32))
        out = client.fetch()  # FIFO barrier: the push has been applied
        assert out.shape == (DIM,)
        # ONE push message and ONE fetch round trip, not two of each
        assert server.counts["push_easgd"] == 1
        assert server.counts["fetch"] == 1
        client.stop()
        thread.join(timeout=5)
        assert server.error is None

    def test_non_adjacent_repeat_accepted(self):
        # the old non-adjacent restriction is lifted: ring placement can
        # hand one rank non-contiguous chunks, and they coalesce into one
        # message per destination (behavior pinned end-to-end in
        # tests/test_sharding.py::TestScatterCoalescing)
        tps = Broker(3).transports()
        client = PClient(tps[2], [0, 1, 0], 12)
        assert client.ranks == [0, 1]
        assert client._rank_chunks[0] == [(0, 4), (8, 12)]

    def test_dedup_holds_across_coalesced_envelope(self):
        tps, server, thread = self._world()
        client = PClient(tps[1], [0, 0], DIM, timeout=5)
        flat = np.ones(DIM, np.float32)
        client.push_easgd(flat)
        # a retry re-offers the identical coalesced envelope (same epoch,
        # same seq, the full merged chunk) — replay it verbatim
        tps[1].send(
            0, TAG_PUSH_EASGD, (client._epoch, 1, 0, flat)
        )
        client.fetch()  # FIFO barrier
        assert server.counts["push_easgd"] == 1
        assert server.counts["dup_dropped"] == 1
        client.stop()
        thread.join(timeout=5)
        assert server.error is None

    def test_multi_chunk_param_reply_concatenates(self):
        # a sharded server may answer one coalesced FETCH with its
        # per-shard chunks in a single message: list-of-parts replies
        # reassemble (mixing raw and quantized parts)
        tps = Broker(2).transports()
        client = PClient(tps[1], [0], 12, timeout=5)
        a = np.arange(8, dtype=np.float32)
        b = np.arange(8, 12, dtype=np.float32)
        whole = np.concatenate([a, b])
        assert np.array_equal(client._chunk_ok([a, b], 12), whole)
        got = client._chunk_ok([a, quantize(b, "bf16")], 12)
        np.testing.assert_allclose(got, whole, rtol=2 ** -8)
        # malformed lists are rejected, not crashed on
        assert client._chunk_ok([], 12) is None
        assert client._chunk_ok([a], 12) is None


class TestQuantizedExchange:
    def test_int8_easgd_with_ef_converges(self):
        tps = Broker(2).transports()
        server = PServer(
            tps[0], np.zeros(DIM, np.float32), num_clients=1,
            alpha=0.5, quant="int8",
        )
        thread = spawn_server_thread(server)
        client = PClient(tps[1], [0], DIM, timeout=5, quant="int8")
        rng = np.random.default_rng(11)
        target = rng.standard_normal(DIM).astype(np.float32)
        for _ in range(60):
            center = client.fetch()  # quantized PARAM reply, dequantized
            client.push_easgd(target)
        # without EF the int8 push bias would floor the center error near
        # the quantization step; with it the TRUE center converges well
        # inside it (the fetch view adds one un-fed-back snapshot
        # quantization, so it is only step-accurate)
        snap = server.snapshot()
        step = float(np.max(np.abs(snap))) / 127.0
        err = float(np.max(np.abs(snap - target)))
        assert err < step / 2, (err, step)
        fetch_err = float(np.max(np.abs(client.fetch() - target)))
        assert fetch_err <= err + step / 2 + 1e-6, (fetch_err, step)
        client.stop()
        thread.join(timeout=5)
        assert server.error is None

    def test_unversioned_fetch_never_gets_quantized_reply(self):
        # a legacy client (no attempt id) cannot dequantize — the server
        # must answer it with the raw snapshot even when quant is on
        tps = Broker(2).transports()
        server = PServer(
            tps[0], np.full(DIM, 2.0, np.float32), num_clients=1,
            quant="int8",
        )
        thread = spawn_server_thread(server)
        from mpit_tpu.parallel.pserver import TAG_FETCH, TAG_PARAM

        tps[1].send(0, TAG_FETCH, None)  # legacy un-id'd FETCH
        msg = tps[1].recv(0, TAG_PARAM, timeout=5)
        assert isinstance(msg.payload, np.ndarray)
        np.testing.assert_array_equal(
            msg.payload, np.full(DIM, 2.0, np.float32)
        )
        from mpit_tpu.parallel.pserver import TAG_STOP

        tps[1].send(0, TAG_STOP, None)
        thread.join(timeout=5)
        assert server.error is None

    def test_quant_validation(self):
        tps = Broker(2).transports()
        with pytest.raises(ValueError, match="quant"):
            PServer(
                tps[0], np.zeros(4, np.float32), num_clients=1,
                quant="fp8",
            )


def _free_ports(n):
    import socket as _socket

    probes, addrs = [], []
    for _ in range(n):
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        addrs.append(("127.0.0.1", s.getsockname()[1]))
        probes.append(s)
    for s in probes:
        s.close()
    return addrs


class TestMixedVersionSocket:
    """A framed-capable peer and a pickle-only peer (emulated with
    MPIT_WIRE_NEGOTIATE=0 — no hello sent, none awaited, nothing framed)
    must complete real EASGD exchanges in BOTH pairings: negotiation
    falls the framed side back to pickle, and protocol semantics are
    format-independent."""

    @pytest.mark.parametrize("legacy_side", ["server", "client"])
    def test_two_round_easgd_exchange(self, legacy_side, monkeypatch):
        # keep the framed side's hello wait short: the legacy peer will
        # never send one and the connect path eats the full timeout
        monkeypatch.setenv("MPIT_WIRE_NEGOTIATE_TIMEOUT_S", "0.3")
        addrs = _free_ports(2)

        def build(rank, legacy):
            if legacy:
                monkeypatch.setenv("MPIT_WIRE_NEGOTIATE", "0")
            else:
                monkeypatch.delenv("MPIT_WIRE_NEGOTIATE", raising=False)
            return SocketTransport(rank, 2, addresses=addrs)

        srv_tp = build(0, legacy_side == "server")
        cli_tp = build(1, legacy_side == "client")
        alpha = 0.5
        server = PServer(
            srv_tp, np.zeros(DIM, np.float32), num_clients=1, alpha=alpha,
        )
        thread = spawn_server_thread(server)
        client = PClient(cli_tp, [0], DIM, timeout=10)
        try:
            ones = np.ones(DIM, np.float32)
            c0 = client.fetch()
            np.testing.assert_array_equal(c0, np.zeros(DIM))
            client.push_easgd(ones)  # center += alpha * (x - center)
            c1 = client.fetch()
            np.testing.assert_allclose(c1, alpha * ones, rtol=1e-6)
            client.push_easgd(ones)
            c2 = client.fetch()
            np.testing.assert_allclose(
                c2, (alpha + alpha * (1 - alpha)) * ones, rtol=1e-6
            )
            assert server.counts["push_easgd"] == 2
            assert server.counts["fetch"] == 3
        finally:
            client.stop()
            thread.join(timeout=10)
            srv_tp.close()
            cli_tp.close()
        assert server.error is None
