#!/usr/bin/env python3
"""Load generator: closed-loop WebSocket RPC clients, in a process of their own.

`run.py` starts this file with `subprocess`, once per client process of the
traffic mix, and hands it one JSON spec file:

    {"url": "ws://127.0.0.1:<port>/rpc", "ns": ..., "db": ..., "seed": n,
     "client_ids": [0, 1, ...], "think_time_s": 0.0,
     "statements": [{"name", "weight", "sql", "bind", "pool": <file.json>}],
     "out": <records file>}

Every client is one WebSocket session (one thread). It walks its statement's
pool in an order drawn from the seed and its own id, sends the next request
only when the last reply has been decoded (closed loop), and stamps each
request with `time.perf_counter()` before the send and after the decode
(CLOCK_MONOTONIC: the same clock in every process of the machine). Commands
arrive as lines on stdin: `tag <seconds>` makes each client carry a trace id
of its own on at most one request per that many seconds, `stop` ends the run.
At the end the records go to `out` as JSON and the process exits 0.

This process never imports JAX: the chip belongs to the server's process, and
a client that held the server's interpreter lock would measure itself.
"""

from __future__ import annotations

import json
import os
import queue
import random
import sys
import threading
import time
import uuid

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from surrealdb_tpu.net import ws as wsproto  # noqa: E402
from surrealdb_tpu.sdk.remote import WsEngine  # noqa: E402
from surrealdb_tpu.utils.ser import wire_pack  # noqa: E402


class TracedWs(WsEngine):
    """The SDK's WebSocket engine, with the RPC frame's optional `trace`
    field (the server then keeps that request's span tree under this id).
    Returns the whole reply, so an error reply is a record and not a raise."""

    def call(self, method: str, params: list, trace=None) -> dict:
        mid = next(self._ids)
        q: "queue.Queue" = queue.Queue()
        with self._lock:
            if self._dead:
                return {"error": {"message": "the WebSocket connection is closed"}}
            self._pending[mid] = q
        frame = {"id": mid, "method": method, "params": params}
        if trace is not None:
            frame["trace"] = trace
        self.sock.sendall(wsproto.encode_frame(wsproto.OP_BINARY, wire_pack(frame), mask=True))
        msg = q.get()
        return msg if msg is not None else {"error": {"message": "connection closed"}}


def _record_id(rid):
    """A record id's key as the wire carries it (a Thing, or `tb:key` text)."""
    key = getattr(rid, "id", None)
    if key is None:
        key = str(rid).rsplit(":", 1)[-1]
    try:
        return int(key)
    except (TypeError, ValueError):
        return str(key)


def read_answer(msg: dict) -> tuple:
    """(status, ids, values) of one reply: the status is `OK` only if the
    RPC and every statement in it succeeded; ids are the last statement's
    record ids in order, values its other numeric fields by name."""
    if msg.get("error"):
        return "RPC_ERROR: " + str(msg["error"].get("message"))[:200], [], {}
    rows = msg.get("result") or []
    for r in rows:
        if r.get("status") != "OK":
            return f"{r.get('status')}: {str(r.get('result'))[:200]}", [], {}
    ids, values = [], {}
    last = rows[-1].get("result") if rows else None
    for row in last if isinstance(last, list) else []:
        if not isinstance(row, dict):
            continue
        for k, v in row.items():
            if k == "id":
                ids.append(_record_id(v))
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                values.setdefault(k, []).append(v)
    return "OK", ids, values


class Statement:
    def __init__(self, spec: dict):
        self.name, self.weight = spec["name"], float(spec["weight"])
        self.sql, self.bind = spec["sql"], spec["bind"]
        with open(spec["pool"]) as f:
            self.pool = json.load(f)

    def request(self, i: int) -> list:
        """The `query` RPC's params for pool entry i."""
        if self.bind == "inline":
            return [self.sql.replace("{arg}", str(self.pool[i]))]
        return [self.sql, {self.bind: self.pool[i]}]


class Client(threading.Thread):
    def __init__(self, cid: int, spec: dict, statements: list, shared: dict):
        super().__init__(name=f"client-{cid}", daemon=True)
        self.cid, self.spec, self.statements, self.shared = cid, spec, statements, shared
        self.records: list = []
        self.error = None

    def run(self) -> None:
        try:
            self._run()
        except Exception as e:  # noqa: BLE001 — reported in the records file, then fatal in run.py
            self.error = repr(e)[:300]

    def _run(self) -> None:
        spec, shared = self.spec, self.shared
        rng = random.Random(spec["seed"] * 100_003 + self.cid)
        orders = []
        for st in self.statements:
            order = list(range(len(st.pool)))
            rng.shuffle(order)
            orders.append(order)
        cursors = [0] * len(self.statements)
        weights = [st.weight for st in self.statements]
        ws = TracedWs(spec["url"])
        try:
            ws.call("use", [spec["ns"], spec["db"]])
            last_tag = -1e18
            while not shared["stop"].is_set():
                si = rng.choices(range(len(self.statements)), weights)[0] if len(weights) > 1 else 0
                st, order = self.statements[si], orders[si]
                qi = order[cursors[si] % len(order)]
                cursors[si] += 1
                params = st.request(qi)
                tid = None
                every = shared["tag_every_s"]
                if every is not None and time.perf_counter() - last_tag >= every:
                    tid = uuid.uuid4().hex
                t0 = time.perf_counter()
                msg = ws.call("query", params, tid)
                t1 = time.perf_counter()
                if tid is not None:
                    last_tag = t0
                status, ids, values = read_answer(msg)
                self.records.append(
                    {"c": self.cid, "s": st.name, "q": qi, "t0": t0, "t1": t1,
                     "status": status, "ids": ids, "values": values, "trace": tid}
                )
                if spec["think_time_s"] > 0:
                    time.sleep(spec["think_time_s"])
        finally:
            ws.close()


def main(argv) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    statements = [Statement(s) for s in spec["statements"]]
    shared = {"stop": threading.Event(), "tag_every_s": None}
    clients = [Client(cid, spec, statements, shared) for cid in spec["client_ids"]]
    for c in clients:
        c.start()
    print("started", flush=True)
    for line in sys.stdin:
        word, _, arg = line.strip().partition(" ")
        if word == "tag":
            shared["tag_every_s"] = float(arg)
        elif word == "stop":
            break
    shared["stop"].set()
    for c in clients:
        c.join(timeout=600.0)
    out = {
        "records": [r for c in clients for r in c.records],
        "errors": [c.error for c in clients if c.error]
        + [f"client {c.cid} did not stop" for c in clients if c.is_alive()],
        "jax_imported": "jax" in sys.modules,
        "pid": os.getpid(),
    }
    with open(spec["out"], "w") as f:
        json.dump(out, f)
    assert "jax" not in sys.modules, "the load generator imported JAX"
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
