"""Key-value storage abstraction.

Reference behavior: storage/kv_store.py:5 — KeyValueStorage ABC with
put/get/remove/iterator/do_ops_in_batch over LevelDB/RocksDB/memory/file
backends. Keys and values are bytes; int keys are encoded big-endian so
lexicographic iteration equals numeric order (ref kv_store_leveldb_int_keys.py).
"""
from __future__ import annotations

from abc import ABC, abstractmethod
from contextlib import contextmanager
from typing import Iterable, Iterator, Optional, Tuple


def encode_key(key) -> bytes:
    if isinstance(key, bytes):
        return key
    if isinstance(key, str):
        return key.encode()
    if isinstance(key, int):
        return key.to_bytes(8, "big")
    raise TypeError(f"unsupported key type {type(key)}")


def new_io() -> dict:
    """What a durable backend counts of its own traffic: rows and bytes
    appended, flushes handed to the OS and the seconds they took, `get`s
    answered from the file. The memory store keeps no such record."""
    return {"rows": 0, "bytes": 0, "flushes": 0, "flush_s": 0.0, "gets": 0}


def decode_int_key(key: bytes) -> int:
    return int.from_bytes(key, "big")


class KeyValueStorage(ABC):
    @abstractmethod
    def put(self, key, value: bytes) -> None: ...

    @abstractmethod
    def get(self, key) -> bytes: ...   # raises KeyError if absent

    @abstractmethod
    def remove(self, key) -> None: ...

    @abstractmethod
    def iterator(self, start=None, end=None, include_value: bool = True) -> Iterator: ...

    @abstractmethod
    def close(self) -> None: ...

    def try_get(self, key) -> Optional[bytes]:
        try:
            return self.get(key)
        except KeyError:
            return None

    def has_key(self, key) -> bool:
        return self.try_get(key) is not None

    @contextmanager
    def write_batch(self):
        """Group every put/remove issued inside the scope into one backend
        write. Durable backends override this to emit a SINGLE atomic batch
        record (one syscall, one flush, all-or-nothing on crash replay) —
        the group-commit primitive the 3PC durable path rides. Default:
        no-op grouping (each op applies immediately), which is exact for
        memory-only stores. Reads inside the scope observe the writes.
        Nested scopes join the outermost batch."""
        yield self

    def do_ops_in_batch(self, batch: Iterable[Tuple[str, object, bytes]]) -> None:
        """batch of ('put'|'remove', key, value) applied as ONE atomic
        backend write where the backend supports it (write_batch)."""
        with self.write_batch():
            for op, key, value in batch:
                if op == "put":
                    self.put(key, value)
                elif op == "remove":
                    self.remove(key)
                else:
                    raise ValueError(f"unknown op {op}")

    @property
    @abstractmethod
    def size(self) -> int: ...
