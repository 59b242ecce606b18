"""The server's wire protocol, as the benchmark's own client speaks it.

Newline-delimited JSON, one request and one response in order per
connection (``repro.serve.protocol``).  This copy exists so that the load
generator's child process never imports ``repro.serve``, whose package
imports JAX: a process that imports JAX may take the chip from the server.
"""

from __future__ import annotations

import json
import socket
from typing import Optional

import numpy as np


def encode(msg: dict) -> bytes:
    return json.dumps(msg, separators=(",", ":")).encode("utf-8") + b"\n"


def decode(line: bytes) -> dict:
    msg = json.loads(line.decode("utf-8"))
    if not isinstance(msg, dict):
        raise ValueError(f"frame is not a JSON object: {type(msg)}")
    return msg


class Connection:
    """One closed-loop connection: send a request, read its response."""

    def __init__(self, host: str, port: int, timeout_s: float = 120.0):
        self._sock = socket.create_connection((host, port),
                                              timeout=timeout_s)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._f = self._sock.makefile("rwb")
        self._next_id = 0

    def request(self, op: str, **fields) -> dict:
        self._next_id += 1
        self._f.write(encode({"id": self._next_id, "op": op, **fields}))
        self._f.flush()
        line = self._f.readline()
        if not line:
            raise ConnectionError("server closed the connection")
        resp = decode(line)
        if resp.get("id") not in (self._next_id, None):
            raise ConnectionError(f"response {resp.get('id')} for request "
                                  f"{self._next_id}")
        return resp

    def query(self, tenant: str, rows: np.ndarray, k: int,
              n_probes: int) -> dict:
        return self.request("query", tenant=tenant,
                            queries=np.asarray(rows, np.float32).tolist(),
                            k=int(k), n_probes=int(n_probes))

    def insert(self, tenant: str, rows: np.ndarray) -> dict:
        return self.request("insert", tenant=tenant,
                            embeddings=np.asarray(rows, np.float32).tolist())

    def delete(self, tenant: str, gids) -> dict:
        return self.request("delete", tenant=tenant,
                            gids=[int(g) for g in gids])

    def close(self) -> None:
        try:
            self._f.close()
        finally:
            self._sock.close()


def answer_arrays(resp: dict) -> Optional[tuple]:
    """(gids int32, dists float32) of an ok query response, else None."""
    if not resp.get("ok"):
        return None
    return (np.asarray(resp["gids"], np.int32),
            np.asarray(resp["dists"], np.float32))
