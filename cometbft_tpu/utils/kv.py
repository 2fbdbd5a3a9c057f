"""Embedded KV store abstraction (the reference's cometbft-db seam).

Two backends: in-memory dict (tests, like memdb) and sqlite3 (durable,
transactional, ships with CPython — the role goleveldb/pebble plays for
the reference). Keys/values are bytes; batches are atomic.
"""

from __future__ import annotations

import sqlite3
import threading
from typing import Dict, Iterator, List, Optional, Tuple


class KV:
    def get(self, key: bytes) -> Optional[bytes]:
        raise NotImplementedError

    def set(self, key: bytes, value: bytes) -> None:
        raise NotImplementedError

    def delete(self, key: bytes) -> None:
        raise NotImplementedError

    def write_batch(self, sets, deletes=()) -> None:
        """Atomic batch: sets = [(k, v)], deletes = [k]."""
        raise NotImplementedError

    def iter_prefix(self, prefix: bytes) -> Iterator[Tuple[bytes, bytes]]:
        raise NotImplementedError

    def close(self) -> None:
        pass


class MemKV(KV):
    def __init__(self):
        self._d: Dict[bytes, bytes] = {}
        self._lock = threading.RLock()

    def get(self, key):
        with self._lock:
            return self._d.get(key)

    def set(self, key, value):
        with self._lock:
            self._d[bytes(key)] = bytes(value)

    def delete(self, key):
        with self._lock:
            self._d.pop(key, None)

    def write_batch(self, sets, deletes=()):
        with self._lock:
            for k, v in sets:
                self._d[bytes(k)] = bytes(v)
            for k in deletes:
                self._d.pop(k, None)

    def iter_prefix(self, prefix):
        with self._lock:
            items = sorted(
                (k, v) for k, v in self._d.items() if k.startswith(prefix)
            )
        yield from items


class SqliteKV(KV):
    def __init__(self, path: str):
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS kv (k BLOB PRIMARY KEY, v BLOB)"
        )
        self._conn.execute("PRAGMA journal_mode=WAL")
        self._conn.execute("PRAGMA synchronous=NORMAL")
        self._conn.commit()
        self._lock = threading.RLock()

    def get(self, key):
        with self._lock:
            row = self._conn.execute(
                "SELECT v FROM kv WHERE k = ?", (key,)
            ).fetchone()
        return row[0] if row else None

    def set(self, key, value):
        with self._lock:
            self._conn.execute(
                "INSERT OR REPLACE INTO kv (k, v) VALUES (?, ?)", (key, value)
            )
            self._conn.commit()

    def delete(self, key):
        with self._lock:
            self._conn.execute("DELETE FROM kv WHERE k = ?", (key,))
            self._conn.commit()

    def write_batch(self, sets, deletes=()):
        with self._lock:
            self._conn.executemany(
                "INSERT OR REPLACE INTO kv (k, v) VALUES (?, ?)", list(sets)
            )
            if deletes:
                self._conn.executemany(
                    "DELETE FROM kv WHERE k = ?", [(k,) for k in deletes]
                )
            self._conn.commit()

    def iter_prefix(self, prefix):
        hi = prefix + b"\xff" * 8
        with self._lock:
            rows = self._conn.execute(
                "SELECT k, v FROM kv WHERE k >= ? AND k <= ? ORDER BY k",
                (prefix, hi),
            ).fetchall()
        for k, v in rows:
            if bytes(k).startswith(prefix):
                yield bytes(k), bytes(v)

    def close(self):
        # under the lock: closing the connection in the middle of
        # another thread's write_batch is a segfault inside sqlite
        with self._lock:
            self._conn.close()


def open_kv(backend: str, path: Optional[str] = None) -> KV:
    if backend == "memdb":
        return MemKV()
    if backend == "sqlite":
        assert path
        return SqliteKV(path)
    if backend == "logdb":
        # native C++ log-structured engine (the reference's pebble role)
        assert path
        from .logdb import LogDB

        return LogDB(path)
    raise ValueError(f"unknown db backend {backend}")
