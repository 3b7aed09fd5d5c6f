"""RFC 6455 §5.3 masking in one pass: `net/ws.py::apply_mask` against the
per-byte definition, through `encode_frame` / `read_frame`, and through the
event loop's `_ws_frames` on the served path."""

import os
import socket
import time

import numpy as np
import pytest

from surrealdb_tpu.net import ws as wsproto
from surrealdb_tpu.net.server import serve
from surrealdb_tpu.utils.ser import wire_pack, wire_unpack

LENGTHS = [0, 1, 2, 3, 4, 5, 125, 126, 127, 65_535, 65_536, 1 << 20]
KEYS = {
    "random": None,
    "zero_first": b"\x00\x9c\x41\xe7",
    "zero_inside": b"\x5a\x00\x00\x13",
    "all_zero": b"\x00\x00\x00\x00",
}
KNN_SQL = "SELECT id, vector::distance::knn() AS d FROM item WHERE emb <|10,64|> $q"


def per_byte(payload: bytes, key: bytes) -> bytes:
    """The plain reference: the RFC's definition, an octet at a time."""
    return bytes(b ^ key[i % 4] for i, b in enumerate(payload))


@pytest.mark.parametrize("key_kind", list(KEYS))
@pytest.mark.parametrize("n", LENGTHS)
def test_apply_mask_is_the_per_byte_definition(n, key_kind):
    key = KEYS[key_kind] or os.urandom(4)
    payload = os.urandom(n)
    masked = wsproto.apply_mask(payload, key)
    assert type(masked) is bytes and len(masked) == n
    assert masked == per_byte(payload, key)
    # an involution: unmasking is the same call
    assert wsproto.apply_mask(masked, key) == payload


BUFFERS = {
    "bytearray": bytearray,
    "memoryview": memoryview,
    "odd_offset": lambda p: memoryview(b"x" + p)[1:],  # words that start off a 4-byte boundary
}


@pytest.mark.parametrize("kind", list(BUFFERS))
def test_apply_mask_takes_any_buffer(kind):
    payload, key = os.urandom(7_063), os.urandom(4)
    assert wsproto.apply_mask(BUFFERS[kind](payload), key) == per_byte(payload, key)


class _Reader:
    """A `read()` source that hands out at most `step` bytes a call, as a
    socket's short reads do."""

    def __init__(self, data: bytes, step: int = 4_099):
        self.data, self.step = data, step

    def read(self, n: int) -> bytes:
        n = min(n, self.step)
        out, self.data = self.data[:n], self.data[n:]
        return out


@pytest.mark.parametrize("n", [0, 125, 126, 65_535, 65_536, 70_001])
def test_masked_frame_round_trips_at_each_length_encoding(n):
    payload = os.urandom(n)
    frame = wsproto.encode_frame(wsproto.OP_BINARY, payload, mask=True)
    head = 2 + (0 if n < 126 else 2 if n < 65_536 else 8)
    assert frame[1] & 0x80 and frame[1] & 0x7F == (n if n < 126 else 126 if n < 65_536 else 127)
    key = frame[head:head + 4]
    assert frame[head + 4:] == per_byte(payload, key)
    assert wsproto.read_frame(_Reader(frame)) == (wsproto.OP_BINARY, payload)
    # unmasked frames are untouched
    assert wsproto.encode_frame(wsproto.OP_BINARY, payload)[head:] == payload


def test_each_frame_draws_its_own_key():
    frames = [wsproto.encode_frame(wsproto.OP_TEXT, b"x" * 8, mask=True) for _ in range(8)]
    assert len({f[2:6] for f in frames}) > 1


def _fragment(chunk: bytes, first: bool, last: bool) -> bytes:
    """One masked fragment of a binary message, its key the frame's own."""
    frame = bytearray(wsproto.encode_frame(wsproto.OP_BINARY if first else wsproto.OP_CONT, chunk, mask=True))
    if not last:
        frame[0] &= 0x7F  # FIN off
    return bytes(frame)


def test_read_frame_unmasks_each_fragment_with_its_own_key():
    parts = [os.urandom(n) for n in (5, 126, 3)]
    data = b"".join(_fragment(p, first=i == 0, last=i == 2) for i, p in enumerate(parts))
    assert wsproto.read_frame(_Reader(data)) == (wsproto.OP_BINARY, b"".join(parts))


@pytest.fixture(scope="module")
def served():
    """A loop-served WebSocket session over a small 768-d table."""
    from surrealdb_tpu import cnf
    from surrealdb_tpu.dbs.session import Session

    srv = serve("memory", port=0, auth_enabled=False).start_background()
    try:
        assert srv.loop_mode and cnf.NET_LOOP  # `_ws_frames` is the event loop's
        s = Session.owner("t", "t")
        rng = np.random.default_rng(41)
        ddl = "DEFINE TABLE item; DEFINE INDEX ix ON item FIELDS emb HNSW DIMENSION 768 DIST EUCLIDEAN EFC 64;"
        assert all(r["status"] == "OK" for r in srv.ds.execute(ddl, s))
        rows = [{"id": i, "emb": rng.normal(size=768).astype(np.float32).tolist()} for i in range(64)]
        assert srv.ds.execute("INSERT INTO item $rows RETURN NONE", s, vars={"rows": rows})[-1]["status"] == "OK"
        sock = socket.create_connection((srv.host, srv.port), timeout=30)
        bs = wsproto.BufferedSocket(sock, wsproto.client_handshake(sock, f"{srv.host}:{srv.port}", "/rpc"))

        def rpc(req, send=None):
            body = wire_pack(req)
            sock.sendall(send(body) if send else wsproto.encode_frame(wsproto.OP_BINARY, body, mask=True))
            return wire_unpack(wsproto.read_frame(bs)[1])

        assert rpc({"id": 0, "method": "use", "params": ["t", "t"]}).get("error") is None
        yield {"rpc": rpc, "q": rng.normal(size=768).tolist(), "srv": srv, "session": s}
        sock.close()
    finally:
        srv.shutdown()
        srv.ds.close()


def _knn_request(served, rid):
    return {"id": rid, "method": "query", "params": [KNN_SQL, {"q": served["q"]}], "trace": f"mask-{rid}"}


def _answer(reply):
    (stmt,) = reply["result"]
    assert stmt["status"] == "OK", stmt
    return [(str(r["id"]), r["d"]) for r in stmt["result"]]


def test_masked_7kb_knn_query_answers_as_the_unmasked_one(served):
    req = _knn_request(served, 1)
    assert 6_900 < len(wire_pack(req)) < 7_300  # the frame the vector cells send
    masked = _answer(served["rpc"](req))
    unmasked = _answer(served["rpc"](dict(req, id=2), send=lambda b: wsproto.encode_frame(wsproto.OP_BINARY, b)))
    assert len(masked) == 10 and masked == unmasked
    # and as the embedded entry point, which no frame reaches
    out = served["srv"].ds.execute(KNN_SQL, served["session"], vars={"q": served["q"]})
    assert [(str(r["id"]), r["d"]) for r in out[-1]["result"]] == masked


def test_three_fragments_each_with_its_own_key_through_the_loop(served):
    req = _knn_request(served, 3)
    whole = _answer(served["rpc"](req))

    def fragments(body):
        cuts = [0, 1_001, len(body) - 2, len(body)]  # lengths not multiples of four
        parts = [_fragment(body[a:b], first=i == 0, last=i == 2)
                 for i, (a, b) in enumerate(zip(cuts, cuts[1:]))]
        assert len({p[4:8] if p[1] & 0x7F == 126 else p[2:6] for p in parts}) == 3
        return b"".join(parts)

    assert _answer(served["rpc"](dict(req, id=4), send=fragments)) == whole


def test_ping_between_requests_is_echoed_unmasked_payload(served):
    srv = served["srv"]
    sock = socket.create_connection((srv.host, srv.port), timeout=30)
    try:
        bs = wsproto.BufferedSocket(sock, wsproto.client_handshake(sock, f"{srv.host}:{srv.port}", "/rpc"))
        sock.sendall(wsproto.encode_frame(wsproto.OP_PING, b"are-you-there", mask=True))
        assert wsproto.read_frame(bs) == (wsproto.OP_PONG, b"are-you-there")
    finally:
        sock.close()


def test_8_mib_is_masked_in_a_pass_no_per_byte_loop_can_make():
    payload, key = bytes(8 << 20), b"\xa5\xc3\x0f\x11"
    t0 = time.perf_counter()
    masked = wsproto.apply_mask(payload, key)
    took = time.perf_counter() - t0
    assert masked[:8] == key * 2 and masked[-4:] == key
    # a generator over 8 MiB takes ~1.5 s on this class of host; one pass ~2 ms
    assert took < 0.5, took
