"""Built-in Kafka wire-protocol client (streams/kafka_wire.py).

Three layers of coverage:
1. GOLDEN FRAMES — requests compared byte-for-byte against independently
   hand-packed frames following the Kafka protocol spec (pins the
   encoding; a fake broker alone would only prove self-consistency).
2. Message-set encode/decode: CRC validation, v0/v1 magic, partial
   trailing message truncation.
3. End-to-end over a REAL TCP socket: a threaded in-process broker
   speaking Metadata/Produce/Fetch/ListOffsets v0/v2 serves
   KafkaSink → topic → kafka_source → windowed range query.
"""

import itertools
import socket
import struct
import threading
import zlib

import numpy as np
import pytest

from spatialflink_tpu.streams import kafka_wire as kw


# ---------- 1. golden frames ----------

def test_metadata_request_golden_bytes():
    body = kw.encode_metadata_request(["gps"])
    frame = kw.encode_request(kw.API_METADATA, 0, 7, "c", body)
    expect = b"".join([
        struct.pack(">i", 2 + 2 + 4 + 2 + 1 + 4 + 2 + 3),  # size
        struct.pack(">h", 3),      # api_key = Metadata
        struct.pack(">h", 0),      # api_version
        struct.pack(">i", 7),      # correlation_id
        struct.pack(">h", 1), b"c",   # client_id
        struct.pack(">i", 1),      # topic array count
        struct.pack(">h", 3), b"gps",
    ])
    assert frame == expect


def test_produce_request_golden_bytes():
    msg_body = b"".join([
        struct.pack(">b", 1),          # magic = 1
        struct.pack(">b", 0),          # attributes
        struct.pack(">q", 1234),       # timestamp
        struct.pack(">i", -1),         # null key
        struct.pack(">i", 2), b"hi",   # value
    ])
    msg = struct.pack(">I", zlib.crc32(msg_body) & 0xFFFFFFFF) + msg_body
    mset = struct.pack(">qi", 0, len(msg)) + msg
    expect_body = b"".join([
        struct.pack(">h", 1),          # acks
        struct.pack(">i", 10_000),     # timeout
        struct.pack(">i", 1),          # topic array
        struct.pack(">h", 1), b"t",
        struct.pack(">i", 1),          # partition array
        struct.pack(">i", 0),          # partition id
        struct.pack(">i", len(mset)),
        mset,
    ])
    got = kw.encode_produce_request(
        "t", 0, kw.encode_message_set([(b"hi", None, 1234)]), acks=1
    )
    assert got == expect_body


def test_fetch_request_golden_bytes():
    expect = b"".join([
        struct.pack(">i", -1),        # replica_id
        struct.pack(">i", 500),       # max_wait_ms
        struct.pack(">i", 1),         # min_bytes
        struct.pack(">i", 1),         # topic array
        struct.pack(">h", 3), b"gps",
        struct.pack(">i", 1),         # partition array
        struct.pack(">i", 2),         # partition
        struct.pack(">q", 42),        # fetch offset
        struct.pack(">i", 1 << 20),   # max_bytes
    ])
    assert kw.encode_fetch_request("gps", 2, 42) == expect


def test_list_offsets_request_golden_bytes():
    expect = b"".join([
        struct.pack(">i", -1),        # replica_id
        struct.pack(">i", 1),
        struct.pack(">h", 1), b"t",
        struct.pack(">i", 1),
        struct.pack(">i", 0),         # partition
        struct.pack(">q", -2),        # EARLIEST
        struct.pack(">i", 1),         # max_offsets (v0)
    ])
    assert kw.encode_list_offsets_request("t", 0, kw.EARLIEST) == expect


# ---------- 2. message sets ----------

def test_message_set_roundtrip_and_crc():
    msgs = [(b"a", None, 10), (b"bb", b"k", 20), (None, None, 30)]
    wire = kw.encode_message_set(msgs)
    out = kw.decode_message_set(wire)
    assert [(v, k, t) for _, t, k, v in out] == msgs
    # Corrupt one payload byte → CRC must catch it.
    bad = bytearray(wire)
    bad[-1] ^= 0xFF
    with pytest.raises(ValueError, match="CRC"):
        kw.decode_message_set(bytes(bad))


def test_message_set_partial_trailing_message():
    wire = kw.encode_message_set([(b"full", None, 1), (b"cutoff", None, 2)])
    out = kw.decode_message_set(wire[:-3])  # broker truncated at max_bytes
    assert len(out) == 1 and out[0][3] == b"full"


def _gzip_wrapper(inner: bytes, wrapper_offset: int, wrapper_ts: int,
                  attrs: int = 0x01, magic: int = 1) -> bytes:
    """Broker-style gzip wrapper message around an inner message set."""
    import gzip as _gzip

    comp = _gzip.compress(inner)
    if magic >= 1:
        body = struct.pack(">bbq", magic, attrs, wrapper_ts)
    else:
        body = struct.pack(">bb", magic, attrs)
    body += kw.enc_bytes(None) + kw.enc_bytes(comp)
    msg = struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF) + body
    return struct.pack(">qi", wrapper_offset, len(msg)) + msg


def test_bootstrap_parsing_portless_and_ipv6():
    c = kw.KafkaWireClient("localhost")
    assert c.bootstrap == [("localhost", 9092)]
    c = kw.KafkaWireClient("[::1]:9093, broker:1234, [fe80::2]")
    assert c.bootstrap == [("::1", 9093), ("broker", 1234),
                           ("fe80::2", 9092)]


def test_gzip_message_set_decodes_with_relative_offsets():
    """KIP-31 v1 wrappers: inner offsets are relative; wrapper offset is
    the absolute offset of the LAST inner message."""
    inner = kw.encode_message_set(
        [(b"a", None, 10), (b"b", b"k", 20), (b"c", None, 30)]
    )  # inner offsets 0,1,2
    wire = _gzip_wrapper(inner, wrapper_offset=41, wrapper_ts=99)
    out = kw.decode_message_set(wire)
    assert [(o, t, k, v) for o, t, k, v in out] == [
        (39, 10, None, b"a"), (40, 20, b"k", b"b"), (41, 30, None, b"c"),
    ]


def test_gzip_log_append_time_overrides_inner_timestamps():
    inner = kw.encode_message_set([(b"a", None, 10), (b"b", None, 20)])
    wire = _gzip_wrapper(inner, wrapper_offset=7, wrapper_ts=555,
                         attrs=0x01 | 0x08)
    out = kw.decode_message_set(wire)
    assert [(o, t) for o, t, _, _ in out] == [(6, 555), (7, 555)]


def test_gzip_magic0_wrapper_keeps_absolute_offsets():
    # magic-0 inner messages with absolute offsets, magic-0 wrapper.
    msgs = []
    for off, val in [(3, b"x"), (4, b"y")]:
        body = struct.pack(">bb", 0, 0) + kw.enc_bytes(None) + kw.enc_bytes(val)
        m = struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF) + body
        msgs.append(struct.pack(">qi", off, len(m)) + m)
    wire = _gzip_wrapper(b"".join(msgs), wrapper_offset=4, wrapper_ts=0,
                         magic=0)
    out = kw.decode_message_set(wire)
    assert [(o, v) for o, _, _, v in out] == [(3, b"x"), (4, b"y")]


def test_snappy_decompress_literals_roundtrip():
    import os
    payload = os.urandom(200_000)  # spans multiple 64k literal chunks
    assert kw.snappy_decompress(kw.snappy_compress_literal(payload)) == payload
    assert kw.snappy_decompress(kw.snappy_compress_literal(b"")) == b""


def test_snappy_decompress_copies_and_xerial():
    # hand-crafted raw stream: literal "abcd" + copy1(off=4, len=4)
    # + copy2(off=2, len=3 overlapping)
    raw = bytes([
        11,            # varint uncompressed length = 11
        (4 - 1) << 2,  # literal, len 4
    ]) + b"abcd" + bytes([
        ((4 - 4) & 7) << 2 | ((4 >> 8) << 5) | 1, 4 & 0xFF,  # copy1 off=4 len=4
        (3 - 1) << 2 | 2, 2, 0,  # copy2 off=2 len=3 (overlapping: "cdc")
    ])
    assert kw.snappy_decompress(raw) == b"abcdabcdcdc"
    # xerial framing: magic + version ints + one length-prefixed block
    framed = (b"\x82SNAPPY\x00" + struct.pack(">ii", 1, 1)
              + struct.pack(">i", len(raw)) + raw)
    assert kw.snappy_decompress(framed) == b"abcdabcdcdc"


def test_snappy_message_set_decodes():
    inner = kw.encode_message_set([(b"a", None, 10), (b"b", b"k", 20)])
    comp = kw.snappy_compress_literal(inner)
    body = struct.pack(">bbq", 1, 0x02, 99) + kw.enc_bytes(None) + kw.enc_bytes(comp)
    msg = struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF) + body
    wire = struct.pack(">qi", 8, len(msg)) + msg
    out = kw.decode_message_set(wire)
    assert [(o, t, k, v) for o, t, k, v in out] == [
        (7, 10, None, b"a"), (8, 20, b"k", b"b"),
    ]


def test_zstd_message_set_still_rejected():
    inner = kw.encode_message_set([(b"a", None, 1)])
    wire = _gzip_wrapper(inner, wrapper_offset=0, wrapper_ts=0, attrs=0x04)
    with pytest.raises(NotImplementedError, match="zstd"):
        kw.decode_message_set(wire)


def test_xxh32_known_vectors():
    """Spec vectors for the pure-python xxHash32 the LZ4 frame checks
    ride on (covers <16-byte tail-only and >16-byte 4-lane paths)."""
    assert kw._xxh32(b"") == 0x02CC5D05
    assert kw._xxh32(b"a") == 0x550D7456
    assert kw._xxh32(b"abc") == 0x32D153FF
    assert kw._xxh32(b"Nobody inspects the spammish repetition") == 0xE2293B2F


def _lz4_frame(blocks, flg=0x60, content=None):
    """Hand-assembled LZ4 frame: list of (data, is_compressed) blocks."""
    header = bytes([flg, 0x40])
    out = bytearray(b"\x04\x22\x4d\x18" + header)
    out.append((kw._xxh32(header) >> 8) & 0xFF)
    for data, is_comp in blocks:
        size = len(data) | (0 if is_comp else 0x80000000)
        out += size.to_bytes(4, "little")
        out += data
    out += (0).to_bytes(4, "little")
    if content is not None:  # flg must carry 0x04
        out += kw._xxh32(content).to_bytes(4, "little")
    return bytes(out)


def test_lz4_decompress_matches_and_overlaps():
    # token lit=10/mlen=11 → "0123456789" + 15-byte copy at offset 10
    blk1 = bytes([0xAB]) + b"0123456789" + b"\x0a\x00"
    want1 = b"0123456789012345678901234"
    # token lit=2/mlen ext: "ab" + 20-byte OVERLAPPING copy at offset 2
    blk2 = bytes([0x2F]) + b"ab" + b"\x02\x00" + bytes([1])
    want2 = b"ab" * 11
    got = kw.lz4_decompress(_lz4_frame([(blk1, True)]))
    assert got == want1
    got = kw.lz4_decompress(_lz4_frame([(blk2, True)]))
    assert got == want2
    # uncompressed block + compressed block in one frame; matches in a
    # later block may reach back into the earlier one (block-dependent
    # frames — flg without the independence bit)
    reach_back = bytes([0x0F]) + b"\x05\x00" + bytes([3])  # 22-byte copy
    got = kw.lz4_decompress(
        _lz4_frame([(b"hello", False), (reach_back, True)], flg=0x40)
    )
    assert got == b"hello" + (b"hello" * 5)[:22]


def test_lz4_roundtrip_and_checksums():
    import os
    payload = os.urandom(200_000)  # spans multiple 64k blocks
    assert kw.lz4_decompress(kw.lz4_compress_literal(payload)) == payload
    assert kw.lz4_decompress(
        kw.lz4_compress_literal(payload, block_checksum=True)
    ) == payload
    assert kw.lz4_decompress(kw.lz4_compress_literal(b"")) == b""
    # the pre-KIP-57 Kafka header-checksum variant is accepted too
    assert kw.lz4_decompress(
        kw.lz4_compress_literal(b"legacy", legacy_hc=True)
    ) == b"legacy"


def test_lz4_corrupt_inputs_raise():
    good = kw.lz4_compress_literal(b"payload payload payload")
    with pytest.raises(ValueError, match="magic"):
        kw.lz4_decompress(b"\x00\x00\x00\x00" + good[4:])
    bad_hc = bytearray(good)
    bad_hc[6] ^= 0xFF  # header checksum byte
    with pytest.raises(ValueError, match="header checksum"):
        kw.lz4_decompress(bytes(bad_hc))
    bad_content = bytearray(good)
    bad_content[-1] ^= 0xFF  # trailing content checksum
    with pytest.raises(ValueError, match="content checksum"):
        kw.lz4_decompress(bytes(bad_content))
    with pytest.raises(ValueError, match="EndMark"):
        kw.lz4_decompress(good[:10])
    bad_blk = bytearray(
        kw.lz4_compress_literal(b"block checksum", block_checksum=True)
    )
    bad_blk[-9] ^= 0xFF  # block checksum (before EndMark + content cksum)
    with pytest.raises(ValueError):
        kw.lz4_decompress(bytes(bad_blk))
    # snappy bytes labeled lz4 must fail loudly, not return garbage
    with pytest.raises((ValueError, IndexError)):
        kw.lz4_decompress(kw.snappy_compress_literal(b"not lz4"))
    # content-size flag set but the header is truncated: ValueError with
    # context, not a bare IndexError (r5 code review)
    with pytest.raises(ValueError, match="truncated header"):
        kw.lz4_decompress(b"\x04\x22\x4d\x18" + bytes([0x48, 0x40, 0x00]))
    # token promises a match but only 1 byte remains for the offset —
    # must raise, not silently decode partial garbage (r5 code review)
    with pytest.raises(ValueError, match="match offset"):
        kw.lz4_block_decompress(b"\x12A\x01", bytearray())
    with pytest.raises(ValueError, match="reserved bit"):
        kw.lz4_decompress(_lz4_frame([], flg=0x62))
    with pytest.raises(ValueError, match="BD byte"):
        bad_bd = bytearray(good)
        bad_bd[5] = 0x30  # block-max code 3: below the legal 4-7 range
        # re-stamp HC so the BD check itself (not HC) is what trips
        bad_bd[6] = (kw._xxh32(bytes(bad_bd[4:6])) >> 8) & 0xFF
        kw.lz4_decompress(bytes(bad_bd))


def test_lz4_literal_frames_respect_declared_block_max():
    """The test encoder must emit frames a SPEC decoder accepts: every
    stored block (token + length ext + literals) within the 64 KiB the
    BD byte declares (r5 code review: 64 KiB chunks overflowed to
    65794-byte blocks)."""
    frame = kw.lz4_compress_literal(b"x" * 200_000)
    pos = 7  # magic + FLG/BD + HC (no content size in these frames)
    sizes = []
    while True:
        bsize = int.from_bytes(frame[pos:pos + 4], "little")
        pos += 4
        if bsize == 0:
            break
        assert not bsize & 0x80000000  # compressed blocks
        sizes.append(bsize)
        pos += bsize
    assert max(sizes) <= 65536
    assert len(sizes) == 4  # 200k / 65200-literal chunks


def test_lz4_message_set_decodes():
    inner = kw.encode_message_set([(b"a", None, 10), (b"b", b"k", 20)])
    comp = kw.lz4_compress_literal(inner)
    body = (struct.pack(">bbq", 1, 0x03, 99)
            + kw.enc_bytes(None) + kw.enc_bytes(comp))
    msg = struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF) + body
    wire = struct.pack(">qi", 8, len(msg)) + msg
    out = kw.decode_message_set(wire)
    assert [(o, t, k, v) for o, t, k, v in out] == [
        (7, 10, None, b"a"), (8, 20, b"k", b"b"),
    ]


def test_snappy_garbage_raises_value_error():
    # attrs=0x02 but the payload is GZIP bytes — the snappy decoder must
    # fail loudly, not return garbage
    inner = kw.encode_message_set([(b"a", None, 1)])
    wire = _gzip_wrapper(inner, wrapper_offset=0, wrapper_ts=0, attrs=0x02)
    with pytest.raises((ValueError, IndexError)):
        kw.decode_message_set(wire)


def test_message_set_magic0_decodes():
    body = struct.pack(">bb", 0, 0) + kw.enc_bytes(None) + kw.enc_bytes(b"v0")
    msg = struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF) + body
    wire = struct.pack(">qi", 5, len(msg)) + msg
    [(off, ts, key, value)] = kw.decode_message_set(wire)
    assert (off, ts, key, value) == (5, -1, None, b"v0")


# ---------- 3. in-process TCP broker ----------

class FakeBroker:
    """Threaded single-node broker: Metadata v0, Produce v2, Fetch v2,
    ListOffsets v0; auto-creates topics with ``num_partitions``."""

    def __init__(self, num_partitions: int = 1):
        self.num_partitions = num_partitions
        # topic → {partition → list[(ts, key, value)]}
        self.logs: dict = {}
        self.fetch_codec = None  # None | gzip | snappy | lz4 | lz4-legacy
        # (topic, partition) → offsets DELETED by log compaction: they
        # stay in the offset sequence but never appear in a fetch.
        self.holes: dict = {}
        # Fault hooks (leader-retry regression tests): ``kill_after_bytes``
        # sends only that many bytes of the NEXT fetch response frame and
        # then kills the connection (a broker dying mid-fetch);
        # ``fetch_errors`` pops one error code per fetch and returns it in
        # the partition response (e.g. 6 = NOT_LEADER — a leader change).
        # Both one-shot-per-entry so the client's retry can succeed.
        self.kill_after_bytes: int = 0
        self.fetch_errors: list = []
        self.metadata_requests = 0
        self.fetch_requests = 0
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self._stop = False
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def close(self):
        self._stop = True
        try:
            self.sock.close()
        except OSError:
            pass

    def _serve(self):
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(
                target=self._handle, args=(conn,), daemon=True
            ).start()

    def _handle(self, conn):
        try:
            while True:
                hdr = self._recv(conn, 4)
                if hdr is None:
                    return
                size = struct.unpack(">i", hdr)[0]
                payload = self._recv(conn, size)
                if payload is None:
                    return
                r = kw.Reader(payload)
                api, ver, corr = r.int16(), r.int16(), r.int32()
                r.string()  # client_id
                body = self._dispatch(api, ver, r)
                resp = struct.pack(">i", corr) + body
                frame = struct.pack(">i", len(resp)) + resp
                if api == kw.API_FETCH and self.kill_after_bytes:
                    # Die mid-response: N bytes of the frame land, then
                    # the socket closes under the client's recv.
                    n, self.kill_after_bytes = self.kill_after_bytes, 0
                    conn.sendall(frame[:n])
                    conn.close()
                    return
                conn.sendall(frame)
        except OSError:
            pass
        finally:
            conn.close()

    @staticmethod
    def _recv(conn, n):
        chunks = []
        while n:
            try:
                c = conn.recv(n)
            except OSError:
                return None
            if not c:
                return None
            chunks.append(c)
            n -= len(c)
        return b"".join(chunks)

    def log(self, topic: str, partition: int = 0) -> list:
        return self.logs.setdefault(topic, {}).setdefault(partition, [])

    def total(self, topic: str) -> int:
        return sum(len(v) for v in self.logs.get(topic, {}).values())

    def _dispatch(self, api, ver, r):
        if api == kw.API_METADATA:
            self.metadata_requests += 1
            topics = [r.string() for _ in range(r.int32())]
            parts = [
                struct.pack(">hiii", 0, p, 0, 1) + struct.pack(">i", 0)
                + struct.pack(">i", 1) + struct.pack(">i", 0)
                for p in range(self.num_partitions)
            ]
            return (
                kw.enc_array([struct.pack(">i", 0)
                              + kw.enc_string("127.0.0.1")
                              + struct.pack(">i", self.port)])
                + kw.enc_array([
                    struct.pack(">h", 0) + kw.enc_string(t)
                    + kw.enc_array(parts)
                    for t in topics
                ])
            )
        if api == kw.API_PRODUCE:
            acks = r.int16()
            r.int32()  # timeout
            out_topics = []
            for _ in range(r.int32()):
                topic = r.string()
                for _ in range(r.int32()):
                    pid = r.int32()
                    mset = r.bytes_() or b""
                    log = self.log(topic, pid)
                    base = len(log)
                    for _off, ts, key, value in kw.decode_message_set(mset):
                        log.append((ts, key, value))
                    out_topics.append(
                        kw.enc_string(topic)
                        + kw.enc_array([struct.pack(">ihqq", pid, 0, base,
                                                    -1)])
                    )
            return kw.enc_array(out_topics) + struct.pack(">i", 0)
        if api == kw.API_FETCH:
            self.fetch_requests += 1
            err_code = self.fetch_errors.pop(0) if self.fetch_errors else 0
            r.int32(), r.int32(), r.int32()  # replica, max_wait, min_bytes
            out_topics = []
            for _ in range(r.int32()):
                topic = r.string()
                for _ in range(r.int32()):
                    pid = r.int32()
                    off = r.int64()
                    r.int32()  # max_bytes
                    if err_code:
                        out_topics.append(
                            kw.enc_string(topic)
                            + kw.enc_array([
                                struct.pack(">ihq", pid, err_code, -1)
                                + kw.enc_bytes(b"")
                            ])
                        )
                        continue
                    log = self.log(topic, pid)
                    holes = self.holes.get((topic, pid), ())
                    msgs = []
                    for i, (ts, key, value) in enumerate(log[off:], start=off):
                        if i in holes:  # compacted away — never served
                            continue
                        m = kw.encode_message_v1(value, key, ts)
                        msgs.append(struct.pack(">qi", i, len(m)) + m)
                    mset = b"".join(msgs)
                    if self.fetch_codec and msgs:
                        mset = self._compressed_wrapper(log, off)
                    out_topics.append(
                        kw.enc_string(topic)
                        + kw.enc_array([
                            struct.pack(">ihq", pid, 0, len(log))
                            + kw.enc_bytes(mset)
                        ])
                    )
            return struct.pack(">i", 0) + kw.enc_array(out_topics)
        if api == kw.API_LIST_OFFSETS:
            r.int32()  # replica
            out_topics = []
            for _ in range(r.int32()):
                topic = r.string()
                for _ in range(r.int32()):
                    pid = r.int32()
                    ts = r.int64()
                    r.int32()  # max_offsets
                    log = self.log(topic, pid)
                    off = 0 if ts == kw.EARLIEST else len(log)
                    out_topics.append(
                        kw.enc_string(topic)
                        + kw.enc_array([
                            struct.pack(">ih", pid, 0)
                            + kw.enc_array([struct.pack(">q", off)])
                        ])
                    )
            return kw.enc_array(out_topics)
        raise AssertionError(f"unexpected api_key {api}")

    def _compressed_wrapper(self, log, off):
        """Broker-style compressed fetch: inner messages with RELATIVE
        offsets (KIP-31) inside one wrapper whose offset is the last
        message's ABSOLUTE offset."""
        import gzip as _gzip

        entries = log[off:]
        rel = []
        for j, (ts, key, value) in enumerate(entries):
            m = kw.encode_message_v1(value, key, ts)
            rel.append(struct.pack(">qi", j, len(m)) + m)
        inner = b"".join(rel)
        comp = {
            "gzip": _gzip.compress,
            "snappy": kw.snappy_compress_literal,
            "lz4": kw.lz4_compress_literal,
            "lz4-legacy": lambda d: kw.lz4_compress_literal(
                d, legacy_hc=True),
        }[self.fetch_codec](inner)
        attrs = {"gzip": 1, "snappy": 2, "lz4": 3, "lz4-legacy": 3}[
            self.fetch_codec]
        body = (struct.pack(">bbq", 1, attrs, -1)
                + kw.enc_bytes(None) + kw.enc_bytes(comp))
        msg = struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF) + body
        return struct.pack(">qi", off + len(entries) - 1, len(msg)) + msg


@pytest.fixture
def broker():
    b = FakeBroker()
    yield b
    b.close()


def _no_libs(monkeypatch):
    """Force the built-in backend even if a kafka lib were importable."""
    import builtins

    real_import = builtins.__import__

    def guarded(name, *a, **kw_):
        if name in ("kafka", "confluent_kafka"):
            raise ImportError(name)
        return real_import(name, *a, **kw_)

    monkeypatch.setattr(builtins, "__import__", guarded)


def test_wire_client_produce_fetch_roundtrip(broker):
    client = kw.KafkaWireClient(f"127.0.0.1:{broker.port}")
    assert client.metadata(["t"]) == {"t": [0]}
    base = client.produce("t", 0, [(b"a", None, 1), (b"b", b"k", 2)])
    assert base == 0
    assert client.list_offset("t", 0, kw.EARLIEST) == 0
    assert client.list_offset("t", 0, kw.LATEST) == 2
    msgs, hw = client.fetch("t", 0, 0)
    assert hw == 2
    assert [(v, k) for _, _, k, v in msgs] == [(b"a", None), (b"b", b"k")]
    # Offset-resumed fetch.
    msgs2, _ = client.fetch("t", 0, 1)
    assert [v for *_, v in msgs2] == [b"b"]
    client.close()


@pytest.mark.parametrize("codec", ["gzip", "snappy", "lz4", "lz4-legacy"])
def test_wire_client_compressed_fetch_roundtrip(broker, codec):
    """Broker-side compression (any fetch may come back compressed,
    whatever the producer sent): KIP-31 relative offsets, timestamps
    and offset-resumed fetches must survive every codec — including
    the pre-KIP-57 legacy lz4 header checksum old brokers emit."""
    client = kw.KafkaWireClient(f"127.0.0.1:{broker.port}")
    client.produce("t", 0, [(b"a", None, 1), (b"b", b"k", 2),
                            (b"c", None, 3)])
    broker.fetch_codec = codec
    msgs, hw = client.fetch("t", 0, 0)
    assert hw == 3
    assert [(o, t, k, v) for o, t, k, v in msgs] == [
        (0, 1, None, b"a"), (1, 2, b"k", b"b"), (2, 3, None, b"c"),
    ]
    msgs2, _ = client.fetch("t", 0, 2)
    assert [(o, v) for o, _, _, v in msgs2] == [(2, b"c")]
    client.close()


def test_multi_partition_timestamp_merge(monkeypatch):
    """Records interleave across 2 partitions in event-time order per
    fetch round (a fixed round-robin would feed the pane paths out of
    order)."""
    _no_libs(monkeypatch)
    from spatialflink_tpu.streams.kafka import kafka_source

    b = FakeBroker(num_partitions=2)
    try:
        client = kw.KafkaWireClient(f"127.0.0.1:{b.port}")
        # even timestamps → partition 0, odd → partition 1
        client.produce("t", 0, [(f"r{t}".encode(), None, t)
                                for t in range(0, 20, 2)])
        client.produce("t", 1, [(f"r{t}".encode(), None, t)
                                for t in range(1, 20, 2)])
        client.close()
        got = list(itertools.islice(
            kafka_source("t", f"127.0.0.1:{b.port}", parser=str), 20
        ))
        assert got == [f"r{t}" for t in range(20)]
    finally:
        b.close()


def test_multi_partition_nonmonotone_ts_no_duplicates(monkeypatch):
    """Within-partition timestamp skew (producer retry / CreateTime)
    must never step a partition's offset backwards — the ts-only merge
    sort can yield a later offset first, and a regressed position would
    re-deliver the earlier record next round (r5 code review)."""
    _no_libs(monkeypatch)
    from spatialflink_tpu.streams.kafka import WireKafkaSource

    b = FakeBroker(num_partitions=2)
    try:
        client = kw.KafkaWireClient(f"127.0.0.1:{b.port}")
        # partition 0: offsets 0,1 carry ts 100, 50 (NON-monotone)
        client.produce("t", 0, [(b"p0a", None, 100), (b"p0b", None, 50)])
        client.produce("t", 1, [(b"p1a", None, 60), (b"p1b", None, 70)])
        client.close()
        src = WireKafkaSource("t", f"127.0.0.1:{b.port}", parser=str)
        got = list(itertools.islice(iter(src), 4))
        src.close()
        assert sorted(got) == ["p0a", "p0b", "p1a", "p1b"], got
        assert len(set(got)) == 4, f"duplicate delivery: {got}"
    finally:
        b.close()


def test_compacted_topic_offset_gap_no_stall_no_dupes(monkeypatch):
    """Log holes (compacted-away offsets) in a multi-partition topic
    must neither stall the position nor re-deliver the post-hole
    records every round: a fetched batch starting past the
    requested position snaps it to the batch's base offset, and within
    the batch the position follows the offsets the broker actually
    delivered — the out-of-sequence parking applies only to the
    ts-sort's reordering of one batch, never to deleted offsets."""
    _no_libs(monkeypatch)
    from spatialflink_tpu.streams.kafka import WireKafkaSource

    b = FakeBroker(num_partitions=2)
    try:
        client = kw.KafkaWireClient(f"127.0.0.1:{b.port}")
        client.produce("t", 0, [(f"p0-{i}".encode(), None, 10 * i)
                                for i in range(6)])
        client.produce("t", 1, [(f"p1-{i}".encode(), None, 10 * i + 5)
                                for i in range(3)])
        client.close()
        # Compaction deleted p0 offsets 0 and 2-3: exercises BOTH the
        # batch-base snap (hole at the requested position) and the
        # within-batch successor chain (hole inside the batch).
        b.holes[("t", 0)] = {0, 2, 3}
        src = WireKafkaSource("t", f"127.0.0.1:{b.port}", parser=str)
        got = list(itertools.islice(iter(src), 6))
        src.close()
        assert sorted(got) == ["p0-1", "p0-4", "p0-5",
                               "p1-0", "p1-1", "p1-2"], got
        assert len(set(got)) == 6, f"duplicate delivery: {got}"
        # The regression trigger: pre-fix, partition 0's position stalls
        # at the hole (0) and every later round re-fetches + re-yields.
        assert src.offsets == {0: 6, 1: 3}, src.offsets
    finally:
        b.close()


def test_mid_round_checkpoint_nonmonotone_ts_no_loss(monkeypatch):
    """A checkpoint taken mid round while ts skew made a LATER offset
    yield first must not skip the earlier, not-yet-yielded record:
    positions advance contiguously, so the resume re-delivers the
    parked record (at-least-once) instead of losing the earlier one
    (r5 code review)."""
    _no_libs(monkeypatch)
    from spatialflink_tpu.streams.kafka import WireKafkaSource

    b = FakeBroker(num_partitions=2)
    try:
        bs = f"127.0.0.1:{b.port}"
        client = kw.KafkaWireClient(bs)
        # partition 0: off 0 carries the LATER ts — it yields second
        client.produce("t", 0, [(b"late", None, 200), (b"early", None, 100)])
        client.produce("t", 1, [(b"mid", None, 150)])
        client.close()
        src1 = WireKafkaSource("t", bs, parser=str)
        first = list(itertools.islice(iter(src1), 1))
        assert first == ["early"]  # off 1, parked out-of-sequence
        snap = src1.offsets
        src1.close()
        assert snap.get(0, 0) == 0, "position must not skip offset 0"
        src2 = WireKafkaSource("t", bs, parser=str, start_offsets=snap)
        rest = list(itertools.islice(iter(src2), 3))
        src2.close()
        # no loss: every record observed across the checkpoint; the
        # parked record may legitimately repeat (at-least-once).
        assert set(first) | set(rest) == {"late", "early", "mid"}
    finally:
        b.close()


def test_kill_and_resume_replays_no_gap_no_dup(monkeypatch):
    """Consumer offsets snapshot through
    checkpoint.py so a killed ingest resumes exactly where it left off —
    the FlinkKafkaConsumer checkpointed-offsets role
    (StreamingJob.java:255). The first consumer is killed MID fetch
    round (both partitions' records buffered in the timestamp merge),
    the hardest consistency point."""
    _no_libs(monkeypatch)
    from spatialflink_tpu.checkpoint import (
        kafka_source_state,
        load_checkpoint,
        restore_kafka_source_offsets,
        save_checkpoint,
    )
    from spatialflink_tpu.streams.kafka import WireKafkaSource

    b = FakeBroker(num_partitions=2)
    try:
        bs = f"127.0.0.1:{b.port}"
        client = kw.KafkaWireClient(bs)
        client.produce("t", 0, [(f"r{t}".encode(), None, t)
                                for t in range(0, 30, 2)])
        client.produce("t", 1, [(f"r{t}".encode(), None, t)
                                for t in range(1, 30, 2)])
        client.close()

        src1 = WireKafkaSource("t", bs, parser=str)
        first = list(itertools.islice(iter(src1), 13))
        assert first == [f"r{t}" for t in range(13)]
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            path = f"{d}/ckpt.pkl"
            save_checkpoint(path, source=kafka_source_state(src1))
            src1.close()  # kill

            state = load_checkpoint(path)["source"]
            with pytest.raises(ValueError, match="topic"):
                restore_kafka_source_offsets(state, "other")
            src2 = WireKafkaSource(
                "t", bs, parser=str,
                start_offsets=restore_kafka_source_offsets(state, "t"),
            )
        rest = list(itertools.islice(iter(src2), 17))
        src2.close()
        assert rest == [f"r{t}" for t in range(13, 30)], \
            "resume must continue exactly after the last yielded record"
    finally:
        b.close()


def test_full_wire_pipeline_kill_and_resume(monkeypatch):
    """THE round-5 resume story end to end over a real socket: Kafka
    CSV records → WireKafkaSource (checkpointed offsets) →
    WirePaneAssembler (checkpointed open-pane buffer) →
    run_wire_panes (checkpointed digest ring). Killed between two
    windows and restored from the three snapshots, the pipeline's
    remaining windows equal an uninterrupted run's exactly.

    Checkpoint alignment note: snapshots are taken between yielded
    windows, i.e. at pane boundaries; the stream's ts deltas stay under
    one slide so a single record never completes more than one pane
    (multi-pane bursts must drain before snapshotting — the barrier
    alignment any checkpointing runtime imposes)."""
    _no_libs(monkeypatch)
    from spatialflink_tpu.checkpoint import (
        kafka_source_state,
        load_checkpoint,
        operator_state,
        restore_kafka_source_offsets,
        restore_operator,
        restore_wire_pane_assembler,
        save_checkpoint,
        wire_pane_assembler_state,
    )
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.models.objects import Point
    from spatialflink_tpu.operators import (
        PointPointKNNQuery,
        QueryConfiguration,
        QueryType,
    )
    from spatialflink_tpu.streams.kafka import WireKafkaSource
    from spatialflink_tpu.streams.wire import WireFormat, WirePaneAssembler

    grid = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
    wf = WireFormat.for_grid(grid)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=4,
                              slide_step=2)
    slide_ms = conf.slide_step_ms
    q, radius, k, nseg = Point(x=5.0, y=5.0), 2.0, 5, 32

    rng = np.random.default_rng(77)
    n = 1_200
    ts = np.cumsum(rng.integers(1, slide_ms // 2, n)).astype(np.int64)
    xy = np.stack([rng.uniform(0, 10, n), rng.uniform(0, 10, n)], axis=1)
    xyf = wf.dequantize_np(wf.quantize(xy))  # the coords on the wire
    oid = rng.integers(0, nseg, n).astype(np.int64)

    b = FakeBroker()
    try:
        bs = f"127.0.0.1:{b.port}"
        client = kw.KafkaWireClient(bs)
        # float() wrap: numpy>=2 reprs f32 scalars as "np.float32(...)"
        # (the CLAUDE.md f-string gotcha — this killed the parser once)
        client.produce("gps", 0, [
            (f"{ts[i]},{float(xyf[i, 0])!r},{float(xyf[i, 1])!r},"
             f"{oid[i]}".encode(), None, int(ts[i]))
            for i in range(n)
        ])
        client.close()

        def parse(line):
            t, x, y, o = line.split(",")
            return int(t), float(x), float(y), int(o)

        def windows(src, asm, op):
            def panes():
                for t, x, y, o in iter(src):
                    for p in asm.feed({"ts": [t], "x": [x], "y": [y],
                                       "oid": [o]}):
                        yield p

            yield from op.run_wire_panes(
                panes(), q, radius, k, nseg, wf, start_ms=0,
                flush_at_end=False,
            )

        def collect(gen, count):
            return [
                (s, e, list(map(int, oo)), [round(float(d), 9) for d in dd])
                for s, e, oo, dd, nv in itertools.islice(gen, count)
            ]

        total = int(ts[-1] // slide_ms) - 2  # full panes only

        src0 = WireKafkaSource("gps", bs, parser=parse)
        asm0 = WirePaneAssembler(wf, slide_ms, start_ms=0)
        baseline = collect(
            windows(src0, asm0, PointPointKNNQuery(conf, grid)), total
        )
        src0.close()

        cut = total // 3
        src1 = WireKafkaSource("gps", bs, parser=parse)
        asm1 = WirePaneAssembler(wf, slide_ms, start_ms=0)
        op1 = PointPointKNNQuery(conf, grid)
        part1 = collect(windows(src1, asm1, op1), cut)
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            path = f"{d}/pipeline.ckpt"
            save_checkpoint(
                path,
                source=kafka_source_state(src1),
                panes=wire_pane_assembler_state(asm1),
                op=operator_state(op1),
            )
            src1.close()  # kill
            del asm1, op1

            snap = load_checkpoint(path)
            src2 = WireKafkaSource(
                "gps", bs, parser=parse,
                start_offsets=restore_kafka_source_offsets(
                    snap["source"], "gps"),
            )
            asm2 = WirePaneAssembler(wf, slide_ms, start_ms=0)
            restore_wire_pane_assembler(asm2, snap["panes"])
            op2 = PointPointKNNQuery(conf, grid)
            restore_operator(op2, snap["op"])
        part2 = collect(windows(src2, asm2, op2), total - cut)
        src2.close()

        assert part1 + part2 == baseline
        assert part1 and part2
        assert sum(len(w[2]) for w in baseline) > 0
    finally:
        b.close()


def test_kafka_available_via_builtin(monkeypatch):
    _no_libs(monkeypatch)
    from spatialflink_tpu.streams.kafka import _import_kafka, kafka_available

    assert kafka_available()
    assert _import_kafka()[0] == "wire"


def test_sink_and_source_over_real_socket(broker, monkeypatch):
    """KafkaSink → wire protocol → broker → kafka_source → windowed range
    query, equal to running the query on the original objects."""
    _no_libs(monkeypatch)
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.models.objects import Point
    from spatialflink_tpu.operators import (
        PointPointRangeQuery,
        QueryConfiguration,
        QueryType,
    )
    from spatialflink_tpu.streams.kafka import KafkaSink, kafka_source
    from spatialflink_tpu.streams.serde import parse_geojson, to_geojson

    grid = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
    rng = np.random.default_rng(9)
    pts = [
        Point(obj_id=f"d{i % 7}", timestamp=int(i * 30),
              x=float(rng.uniform(0, 10)), y=float(rng.uniform(0, 10)))
        for i in range(400)
    ]
    bs = f"127.0.0.1:{broker.port}"
    sink = KafkaSink("gps", bs, formatter=to_geojson, batch=64)
    for p in pts:
        sink(p)
    sink.close()
    assert broker.total("gps") == 400

    stream = itertools.islice(
        kafka_source("gps", bs, parser=parse_geojson), len(pts)
    )
    conf = QueryConfiguration(QueryType.WindowBased, window_size=5,
                              slide_step=5)
    q = Point(x=5.0, y=5.0)

    def results(s):
        return [
            (r.start, r.end, sorted((o.obj_id, o.timestamp) for o in r.objects))
            for r in PointPointRangeQuery(conf, grid).run(s, [q], 2.0)
        ]

    assert results(stream) == results(iter(pts))


def test_wire_source_skips_malformed(broker, monkeypatch):
    _no_libs(monkeypatch)
    from spatialflink_tpu.streams.kafka import kafka_source
    from spatialflink_tpu.streams.serde import parse_csv_point

    client = kw.KafkaWireClient(f"127.0.0.1:{broker.port}")
    client.produce("csv", 0, [
        (b"a,100,1.0,2.0", None, 0),
        (b"not,a,valid,record,###", None, 0),
        (b"", None, 0),
        (b"b,200,3.0,4.0", None, 0),
    ])
    client.close()
    got = list(itertools.islice(
        kafka_source("csv", f"127.0.0.1:{broker.port}",
                     parser=parse_csv_point), 2,
    ))
    assert [p.obj_id for p in got] == ["a", "b"]


# ---------------------------------------------------------------------------
# _with_leader_retry under injected transport faults (ISSUE 8 satellite):
# a broker dying mid-fetch and a leader change must both retry and
# resume at the correct offset — every record delivered exactly once.


def test_mid_fetch_socket_drop_retries_at_same_offset_no_dup(broker):
    """The broker kills the connection after 7 bytes of the fetch
    response frame: the client sees a short read (OSError), drops the
    socket, and _with_leader_retry refetches the SAME offset on a fresh
    connection — no record lost, none duplicated."""
    client = kw.KafkaWireClient(f"127.0.0.1:{broker.port}")
    client.produce("drop", 0, [(f"r{i}".encode(), None, i) for i in range(8)])
    broker.kill_after_bytes = 7  # dies inside the first fetch response
    msgs, hw = client.fetch("drop", 0, 0)
    assert hw == 8
    assert [m[0] for m in msgs] == list(range(8))
    assert [m[3] for m in msgs] == [f"r{i}".encode() for i in range(8)]
    client.close()


def test_mid_fetch_drop_through_source_yields_each_record_once(broker):
    """End to end through WireKafkaSource: the drop lands between two
    consumed batches, and the stream still yields every record exactly
    once in order (the checkpointed-offsets contract survives transport
    faults, not just clean runs)."""
    from spatialflink_tpu.streams.kafka import WireKafkaSource

    client = kw.KafkaWireClient(f"127.0.0.1:{broker.port}")
    client.produce("dropsrc", 0,
                   [(f"a{i}".encode(), None, i) for i in range(5)])
    src = WireKafkaSource("dropsrc", f"127.0.0.1:{broker.port}",
                          parser=str)
    it = iter(src)
    got = [next(it) for _ in range(5)]
    # Arm the mid-frame kill for the NEXT fetch, then extend the log.
    broker.kill_after_bytes = 5
    client.produce("dropsrc", 0,
                   [(f"b{i}".encode(), None, 5 + i) for i in range(5)])
    got += [next(it) for _ in range(5)]
    assert got == [f"a{i}" for i in range(5)] + [f"b{i}" for i in range(5)]
    assert src.offsets == {0: 10}  # resumed at the correct position
    client.close()
    src.close()


def test_leader_change_refreshes_metadata_and_resumes(broker):
    """Error 6 (NOT_LEADER) on a fetch: the client must drop its cached
    leader, re-query metadata, and refetch the same offset — the
    reference gets this from the Flink Kafka connector; the built-in
    client must match it."""
    client = kw.KafkaWireClient(f"127.0.0.1:{broker.port}")
    client.produce("lead", 0, [(f"x{i}".encode(), None, i) for i in range(6)])
    before = broker.metadata_requests
    broker.fetch_errors = [6]  # one leader change
    msgs, _hw = client.fetch("lead", 0, 2)
    assert [m[0] for m in msgs] == [2, 3, 4, 5]
    assert [m[3] for m in msgs] == [f"x{i}".encode() for i in range(2, 6)]
    assert broker.metadata_requests > before  # leader table was refreshed
    assert broker.fetch_requests >= 2  # the failed try + the retry
    client.close()


def test_leader_retry_budget_exhausts_loudly(broker):
    """A leader that NEVER comes back must surface the KafkaError after
    the bounded retries — not spin forever (the r3–r5 lesson: bounded
    beats hung)."""
    client = kw.KafkaWireClient(f"127.0.0.1:{broker.port}")
    client.produce("dead", 0, [(b"v", None, 0)])
    broker.fetch_errors = [6, 6, 6, 6, 6]  # outlives the 3-attempt budget
    with pytest.raises(kw.KafkaError):
        client.fetch("dead", 0, 0)
    client.close()


def test_injected_kafka_leader_fault_is_not_retried(broker):
    """faults.py chaos contract: an InjectedFault at kafka.leader is
    NOT a retriable transport error — it must propagate immediately so
    chaos runs crash deterministically at the armed hit."""
    from spatialflink_tpu.faults import InjectedFault, faults

    client = kw.KafkaWireClient(f"127.0.0.1:{broker.port}")
    client.produce("chaos", 0, [(b"v", None, 0)])
    faults.arm([{"point": "kafka.leader", "at": 1}])
    try:
        with pytest.raises(InjectedFault):
            client.fetch("chaos", 0, 0)
        assert broker.fetch_requests == 0  # died before any attempt
    finally:
        faults.disarm()
        client.close()
