"""Native ingest: build, decode parity, dictionary sync, throughput."""

import time

import numpy as np
import pytest

from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.ingest import BLOCK_MAGIC, BlockEncoder, TsvDecoder, \
    encode_tsv
from theia_tpu.schema import FLOW_SCHEMA
from theia_tpu.store import FlowDatabase
from theia_tpu.utils.native import native_available


@pytest.fixture(scope="module")
def wire():
    batch = generate_flows(SynthConfig(n_series=32, points_per_series=10,
                                       seed=8))
    return batch, encode_tsv(batch)


def test_native_library_builds():
    assert native_available(), "g++ build of native/flowblock.cc failed"


def test_python_fallback_roundtrip(wire):
    batch, payload = wire
    dec = TsvDecoder(force_python=True)
    out = dec.decode(payload)
    assert len(out) == len(batch)
    np.testing.assert_array_equal(out["throughput"],
                                  batch["throughput"])
    np.testing.assert_array_equal(out.strings("sourcePodName"),
                                  batch.strings("sourcePodName"))


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_native_matches_python(wire):
    batch, payload = wire
    nat = TsvDecoder().decode(payload)
    py = TsvDecoder(force_python=True).decode(payload)
    assert len(nat) == len(py) == len(batch)
    for col in FLOW_SCHEMA:
        if col.is_string:
            np.testing.assert_array_equal(
                nat.strings(col.name), py.strings(col.name),
                err_msg=col.name)
        else:
            np.testing.assert_array_equal(
                nat[col.name], py[col.name], err_msg=col.name)


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_native_dictionary_sync_with_store(wire):
    batch, payload = wire
    db = FlowDatabase()
    dec = TsvDecoder(dicts=db.flows.dicts)
    out = dec.decode(payload)
    # decoded batch shares the store dictionaries -> insert is zero-copy
    db.insert_flows(out)
    np.testing.assert_array_equal(
        db.flows.scan().strings("sourceIP"), batch.strings("sourceIP"))
    # decoding again reuses the same codes
    out2 = dec.decode(payload)
    np.testing.assert_array_equal(out2["sourceIP"], out["sourceIP"])


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_native_malformed_row_reports_index():
    dec = TsvDecoder()
    bad = b"not-a-number\t" + b"0\t" * 50 + b"x\n"
    with pytest.raises(ValueError, match="row 0"):
        dec.decode(bad)


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_native_is_fast(wire):
    batch, payload = wire
    reps = 50
    big = payload * reps
    dec = TsvDecoder()
    dec.decode(big)  # warm dictionaries, allocator, page cache
    rate = 0.0
    for _ in range(3):   # best-of-3: tolerate noisy CI boxes
        t0 = time.perf_counter()
        out = dec.decode(big)
        rate = max(rate, len(out) / (time.perf_counter() - t0))
    # Python synth generation runs ~1e5 rows/s; the native decoder must
    # clear 3e5 rows/s even on a loaded CI box (typically >5e5).
    assert rate > 3e5, f"native decode too slow: {rate:,.0f} rows/s"


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_cross_path_dict_additions_stay_in_sync():
    # Strings added to the shared dictionaries by OTHER ingest paths
    # between decodes must not desync native codes (round-2 review).
    db = FlowDatabase()
    dec = TsvDecoder(dicts=db.flows.dicts)
    b1 = generate_flows(SynthConfig(n_series=4, points_per_series=2,
                                    seed=1))
    dec.decode(encode_tsv(b1))
    db.insert_flow_rows([{"sourcePodName": "interloper-pod",
                          "sourceIP": "1.2.3.4"}])
    b2 = generate_flows(SynthConfig(n_series=4, points_per_series=2,
                                    seed=99))
    out = dec.decode(encode_tsv(b2))
    np.testing.assert_array_equal(out.strings("sourceIP"),
                                  b2.strings("sourceIP"))


def test_max_rows_bound_raises_on_both_paths(wire):
    batch, payload = wire
    for force in (False, True):
        dec = TsvDecoder(force_python=force)
        with pytest.raises(ValueError, match="max_rows"):
            dec.decode(payload, max_rows=2)

# -- binary columnar blocks ---------------------------------------------


@pytest.fixture(scope="module")
def block_wire():
    batch = generate_flows(SynthConfig(n_series=32, points_per_series=10,
                                       seed=8))
    enc = BlockEncoder(dicts=batch.dicts)
    return batch, enc, enc.encode(batch)


@pytest.mark.parametrize("force_python", [False, True])
def test_block_roundtrip(block_wire, force_python):
    batch, _, payload = block_wire
    if not force_python and not native_available():
        pytest.skip("no native lib")
    out = TsvDecoder(force_python=force_python).decode_block(payload)
    assert len(out) == len(batch)
    for col in FLOW_SCHEMA:
        if col.is_string:
            np.testing.assert_array_equal(
                out.strings(col.name), batch.strings(col.name),
                err_msg=col.name)
        else:
            np.testing.assert_array_equal(
                np.asarray(out[col.name]), np.asarray(batch[col.name]),
                err_msg=col.name)


def test_block_stream_carries_dictionary_delta(block_wire):
    batch, enc, payload = block_wire
    b2 = generate_flows(SynthConfig(n_series=8, points_per_series=4,
                                    seed=77))
    p2 = enc.encode(b2)   # re-encodes against the encoder's dicts
    dec = TsvDecoder()
    dec.decode_block(payload)
    out2 = dec.decode_block(p2)
    np.testing.assert_array_equal(out2.strings("sourceIP"),
                                  b2.strings("sourceIP"))
    # delta-only: the second block must not repeat already-sent entries
    assert len(p2) < len(payload)


def test_block_out_of_order_is_detected(block_wire):
    batch, enc, payload = block_wire
    p2 = enc.encode(generate_flows(SynthConfig(n_series=8,
                                               points_per_series=4,
                                               seed=78)))
    dec = TsvDecoder()
    with pytest.raises(ValueError, match="desync"):
        dec.decode_block(p2)   # skipped the first block


def test_block_rejects_garbage():
    with pytest.raises(ValueError, match="block"):
        TsvDecoder().decode_block(b"XXXXgarbagegarbagegarbage")


def test_block_decoder_interops_with_tsv_path(block_wire):
    batch, _, payload = block_wire
    dec = TsvDecoder()
    out = dec.decode_block(payload)
    out_tsv = dec.decode(encode_tsv(batch))
    np.testing.assert_array_equal(out["sourceIP"], out_tsv["sourceIP"])


def test_block_decode_is_fast():
    # realistic block size: ~33k rows (tiny blocks are dispatch-bound)
    batch = generate_flows(SynthConfig(n_series=256,
                                       points_per_series=128, seed=3))
    enc = BlockEncoder(dicts=batch.dicts)
    payloads = [enc.encode(batch) for _ in range(6)]
    dec = TsvDecoder()
    dec.decode_block(payloads[0])
    rate = 0.0
    for p in payloads[1:]:   # best-of: tolerate noisy CI boxes
        t0 = time.perf_counter()
        n = len(dec.decode_block(p))
        rate = max(rate, n / (time.perf_counter() - t0))
    # the binary path must beat the TSV path by an order of magnitude
    # (typically >1e7 rows/s; keep slack for loaded CI boxes)
    assert rate > 2e6, f"block decode too slow: {rate:,.0f} rows/s"


def test_truncated_block_does_not_poison_decoder(block_wire):
    batch, _, payload = block_wire
    for force_python in (False, True):
        if not force_python and not native_available():
            continue
        dec = TsvDecoder(force_python=force_python)
        with pytest.raises(ValueError):
            dec.decode_block(payload[:len(payload) // 2])
        # a failed block must leave the decoder fully usable
        out = dec.decode_block(payload)
        np.testing.assert_array_equal(out.strings("sourceIP"),
                                      batch.strings("sourceIP"))


def test_block_with_out_of_range_codes_rejected(block_wire):
    batch, _, _ = block_wire
    enc = BlockEncoder(dicts=batch.dicts)
    good = enc.encode(batch)
    # corrupt the final codes plane (last column is a string column iff
    # schema ends with one; corrupt the very last 4 bytes regardless —
    # for a numeric tail this stays a valid block, so target the known
    # string plane instead: flip bytes across the whole planes section)
    from theia_tpu.schema import FLOW_SCHEMA as _S
    n_rows = len(batch)

    def width(c):
        # TFB2 plane widths: int32 codes / host-width numerics
        return 4 if c.is_string else np.dtype(c.host_dtype).itemsize

    # planes section starts at len(good) - total plane bytes
    plane_bytes = sum(width(c) * n_rows for c in _S)
    start = len(good) - plane_bytes
    # find offset of the first string column's plane
    off = start
    for c in _S:
        if c.is_string:
            break
        off += width(c) * n_rows
    bad = bytearray(good)
    bad[off:off + 4] = (2 ** 31 - 1).to_bytes(4, "little")
    for force_python in (False, True):
        if not force_python and not native_available():
            continue
        dec = TsvDecoder(force_python=force_python)
        with pytest.raises(ValueError, match="codes outside"):
            dec.decode_block(bytes(bad))


def test_block_header_row_bomb_rejected():
    # a 16-byte payload claiming 10^9 rows must not allocate gigabytes
    header = (BLOCK_MAGIC + np.int64(10 ** 9).tobytes()
              + np.int32(len(FLOW_SCHEMA)).tobytes())
    with pytest.raises(ValueError, match="carries only"):
        TsvDecoder().decode_block(header)


def _craft_delta_block(dec, delta_entries):
    """A zero-row block whose first string column carries
    `delta_entries` with a correct base (= the decoder's current
    dictionary size), and empty deltas elsewhere — isolates the
    delta-novelty validation from the base check."""
    parts = [BLOCK_MAGIC, np.int64(0).tobytes(),
             np.int32(len(FLOW_SCHEMA)).tobytes()]
    first = True
    for col in FLOW_SCHEMA:
        if not col.is_string:
            continue
        base = len(dec.dicts[col.name])
        entries = delta_entries if first else []
        first = False
        parts.append(np.asarray([base, len(entries)],
                                np.int32).tobytes())
        for s in entries:
            raw = s.encode()
            parts.append(np.int32(len(raw)).tobytes())
            parts.append(raw)
    return b"".join(parts)   # n_rows=0 → no planes section


def test_block_delta_repeating_existing_entry_rejected(block_wire):
    batch, _, payload = block_wire
    for force_python in (False, True):
        if not force_python and not native_available():
            continue
        dec = TsvDecoder(force_python=force_python)
        dec.decode_block(payload)
        existing = batch.strings("sourceIP")[0]   # already in the dict
        bad = _craft_delta_block(dec, [existing])
        with pytest.raises(ValueError, match="repeats"):
            dec.decode_block(bad)
        # the failure must not poison the decoder
        out = dec.decode(encode_tsv(batch))
        np.testing.assert_array_equal(out.strings("sourceIP"),
                                      batch.strings("sourceIP"))


def test_block_delta_with_intra_delta_duplicate_rejected(block_wire):
    batch, _, payload = block_wire
    for force_python in (False, True):
        if not force_python and not native_available():
            continue
        dec = TsvDecoder(force_python=force_python)
        dec.decode_block(payload)
        bad = _craft_delta_block(dec, ["brand-new", "brand-new"])
        with pytest.raises(ValueError, match="repeats"):
            dec.decode_block(bad)
        # nothing from the rejected delta may have been minted
        assert dec.dicts["sourceIP"].lookup("brand-new") is None


def test_retired_tfb1_payload_is_refused(block_wire):
    """The TFB1 block format (8-byte-widened planes) is retired: a
    payload with its magic is an undecodable payload (ValueError, the
    API's 400), mints nothing and leaves no stream slot behind."""
    from theia_tpu.manager.ingest import IngestManager
    _, _, payload = block_wire
    tfb1 = b"TFB1" + payload[4:]
    im = IngestManager(FlowDatabase(), n_shards=1)
    try:
        with pytest.raises(ValueError):
            im.ingest(tfb1, stream="old-producer")
        assert "old-producer" not in im._streams
        # a stream with state: the refusal resets it like any other
        # undecodable payload, and its dictionaries took nothing in
        assert im.ingest(payload, stream="s")["rows"] > 0
        dicts = im._streams["s"].decoder.dicts
        sizes = {name: len(d) for name, d in dicts.items()}
        with pytest.raises(ValueError):
            im.ingest(tfb1, stream="s")
        assert {name: len(d) for name, d in dicts.items()} == sizes
        assert "s" not in im._streams
        assert len(im.db.flows) == len(block_wire[0])
    finally:
        im.close()

    for force_python in (False, True):
        if not force_python and not native_available():
            continue
        with pytest.raises(ValueError, match="not a flow block"):
            TsvDecoder(force_python=force_python).decode_block(tfb1)
