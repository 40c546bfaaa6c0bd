"""The plain reader of a validator's store files, as they lie on disk.

It decides the comparison `durable.acknowledged_on_fewer_than_2_disks`: after
all four validators were SIGKILLed and before any of them is started again,
which transactions does each one's domain txn log hold? It imports nothing
of plenum_tpu: the two engines' record formats are restated here, so a
change of format in the program shows as writes that are not found.

    .kvn   (native engine, plenum_tpu/native/kvstore.cpp), little-endian:
           u32 crc32 | u8 op | u32 klen | u32 vlen | key | value
           crc32 (zlib's) over op..value; op 0 = put, 1 = delete
    .kvlog (Python KvFile, plenum_tpu/storage/kv_file.py), big-endian:
           u8 op | u32 klen | u32 vlen | key | value
           op 0 = put, 1 = delete, 2 = batch: its value is the scope's
           records one after another, and holds no batch itself

A record that is cut short, fails its checksum or names an unknown op ends
the log: everything before it stands, nothing after it is read (the engines
replay the same way). Nothing is written, truncated or compacted."""
from __future__ import annotations

import os
import struct
import zlib

import msgpack

PUT, DELETE, BATCH = 0, 1, 2
_NATIVE = struct.Struct("<IBII")
_KVLOG = struct.Struct(">BII")


def scan_native(data: bytes, with_ends: bool = False):
    """-> [(op, key, value)] of the sound prefix of a .kvn file (with
    with_ends: also the offset at which each record ends)."""
    out, ends, off = [], [], 0
    while off + _NATIVE.size <= len(data):
        crc, op, klen, vlen = _NATIVE.unpack_from(data, off)
        end = off + _NATIVE.size + klen + vlen
        if op not in (PUT, DELETE) or end > len(data) \
                or zlib.crc32(data[off + 4:end]) != crc:
            break
        body = off + _NATIVE.size
        out.append((op, data[body:body + klen], data[body + klen:end]))
        ends.append(end)
        off = end
    return (out, ends) if with_ends else out


def scan_kvlog(data: bytes, inner: bool = False):
    """-> [(op, key, value)] of the sound prefix of a .kvlog file; a batch
    record gives its inner records, all or none. inner: `data` is a batch
    record's value and has to parse to its end, with no batch in it (None
    where it does not)."""
    out, off = [], 0
    while off + _KVLOG.size <= len(data):
        op, klen, vlen = _KVLOG.unpack_from(data, off)
        end = off + _KVLOG.size + klen + vlen
        if op not in (PUT, DELETE) + (() if inner else (BATCH,)) \
                or end > len(data):
            break
        body = off + _KVLOG.size
        key, value = data[body:body + klen], data[body + klen:end]
        if op == BATCH:
            rows = scan_kvlog(value, inner=True)
            if rows is None:
                break               # the scope's records do not parse
            out.extend(rows)
        else:
            out.append((op, key, value))
        off = end
    if inner and off != len(data):
        return None
    return out


def read_store(directory: str, name: str = "kv") -> dict:
    """One store's directory -> {key: value} as a replay of its log
    leaves it. The native file wins where both exist, as in the program."""
    for suffix, scan in ((".kvn", scan_native), (".kvlog", scan_kvlog)):
        path = os.path.join(directory, name + suffix)
        if os.path.exists(path):
            with open(path, "rb") as fh:
                rows = scan(fh.read())
            break
    else:
        return {}
    live: dict = {}
    for op, key, value in rows:
        if op == PUT:
            live[key] = value
        else:
            live.pop(key, None)
    return live


def ledger_txns(directory: str) -> dict:
    """A ledger's txn log directory -> {seqNo: txn}: keys are 8-byte
    big-endian sequence numbers, values msgpack."""
    return {int.from_bytes(key, "big"):
            msgpack.unpackb(value, raw=False, strict_map_key=False)
            for key, value in read_store(directory).items()}


def requests_of(txns: dict) -> dict:
    """ledger_txns() of a domain txn log -> {(author, reqId): seqNo}."""
    out = {}
    for seq, txn in txns.items():
        meta = (txn.get("txn") or {}).get("metadata") or {}
        out[(meta.get("from"), meta.get("reqId"))] = seq
    return out


def disks_holding(acknowledged: dict, disks: list) -> dict:
    """acknowledged: {(author, reqId): seqNo its REPLY names}; disks: one
    requests_of() per validator -> {key: how many disks hold the write at
    that seqNo}."""
    return {key: sum(1 for disk in disks if disk.get(key) == seq)
            for key, seq in acknowledged.items()}
