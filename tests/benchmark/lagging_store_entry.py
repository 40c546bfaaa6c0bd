"""The durability control's validator: tools/start_node.py unchanged, on
stores that hold every scope's rows back one scope.

A test double wrapped round the native engine in this entry point of the
tests' own; nothing in the program knows it. Rows written inside a
`write_batch` scope reach the engine only when the NEXT scope of that store
closes, while reads see them at once, so the validator behaves as ever and
sends its REPLYs after the scope closed, for rows that are not on disk: the
fault `acknowledged_is_on_disk` exists to catch (a REPLY before the flush, a
flush that became lazier)."""
from __future__ import annotations

import os
import sys
from contextlib import contextmanager


def hold_rows_back_one_scope() -> None:
    from plenum_tpu.storage import kv_native
    from plenum_tpu.storage.kv_store import encode_key
    cls = kv_native.KvNative
    real = {name: getattr(cls, name)
            for name in ("write_batch", "put", "get", "remove")}
    gone = object()

    def held(self) -> dict:
        return self.__dict__.setdefault(
            "_held", {"open": False, "late": [], "now": [], "unwritten": {}})

    @contextmanager
    def write_batch(self):
        h = held(self)
        if h["open"]:
            yield self
            return
        h["open"], h["now"] = True, []
        try:
            yield self
        finally:
            h["open"] = False
            with real["write_batch"](self):
                for row in h["late"]:
                    key, value = row
                    if value is gone:
                        real["remove"](self, key)
                    else:
                        real["put"](self, key, value)
                    if h["unwritten"].get(key, (None,))[0] is row:
                        del h["unwritten"][key]
            h["late"] = h["now"]

    def put(self, key, value):
        h = held(self)
        if not h["open"]:
            return real["put"](self, key, value)
        row = (encode_key(key), bytes(value))
        h["now"].append(row)
        h["unwritten"][row[0]] = (row,)

    def remove(self, key):
        h = held(self)
        if not h["open"]:
            return real["remove"](self, key)
        row = (encode_key(key), gone)
        h["now"].append(row)
        h["unwritten"][row[0]] = (row,)

    def get(self, key):
        hit = held(self)["unwritten"].get(encode_key(key))
        if hit is None:
            return real["get"](self, key)
        if hit[0][1] is gone:
            raise KeyError(key)
        return hit[0][1]

    cls.write_batch, cls.put, cls.get, cls.remove = \
        write_batch, put, get, remove


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    hold_rows_back_one_scope()
    from plenum_tpu.tools import start_node
    start_node.main()
