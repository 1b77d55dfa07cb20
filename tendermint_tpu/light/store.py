"""Trusted light-block store (reference light/store/db/db.go) over kvdb.

A light block of a large validator set is megabytes (1.7 MB at 10,000
validators, as the columns of light/record.py: the store's own record,
which `save` writes and `get` reads, with values an earlier build
pickled still read), and the client asks for the store's heights on every
request:
the heights are in the keys, so every question about WHICH blocks are held
(`heights`, `prune`, and the choice `latest` / `first` / `latest_before`
make before they load their one block) is answered by a key-only scan
(`KVDB.iterate_keys`) and reads no value.  The store counts what it does,
so that a test can hold it to that: `value_reads` and `bytes_read` (every
value taken from the db), `bytes_written`, `pruned`.
"""
from __future__ import annotations

import struct
from bisect import bisect_right
from typing import List, Optional

from tendermint_tpu.libs import trace
from tendermint_tpu.libs.kvdb import KVDB
from tendermint_tpu.light import record
from tendermint_tpu.types.light_block import LightBlock

_PREFIX = b"lb/"


def _key(height: int) -> bytes:
    return _PREFIX + struct.pack(">q", height)


class LightStore:
    def __init__(self, db: KVDB):
        self.db = db
        self.value_reads = 0
        self.bytes_read = 0
        self.bytes_written = 0
        self.pruned = 0

    def save(self, lb: LightBlock) -> None:
        with trace.span("light.store.save", height=lb.height) as sp:
            with trace.span("light.store.encode", height=lb.height) as enc:
                raw, kind = record.encode(lb)
                enc.add(record=kind)
            self.db.set(_key(lb.height), raw)
            self.bytes_written += len(raw)
            sp.add(bytes=len(raw))

    def get(self, height: int) -> Optional[LightBlock]:
        with trace.span("light.store.load", height=height) as sp:
            raw = self.db.get(_key(height))
            sp.add(bytes=len(raw) if raw is not None else 0)
            if raw is None:
                return None
            self.value_reads += 1
            self.bytes_read += len(raw)
            with trace.span("light.store.decode", height=height) as dec:
                lb, kind = record.decode(raw)
                dec.add(record=kind)
            return lb

    def heights(self) -> List[int]:
        """Ascending; from the keys alone."""
        return sorted(struct.unpack(">q", k[len(_PREFIX):])[0]
                      for k in self.db.iterate_keys(_PREFIX))

    def latest(self) -> Optional[LightBlock]:
        hs = self.heights()
        return self.get(hs[-1]) if hs else None

    def first(self) -> Optional[LightBlock]:
        hs = self.heights()
        return self.get(hs[0]) if hs else None

    def latest_before(self, height: int) -> Optional[LightBlock]:
        """The newest block at or below `height`."""
        hs = self.heights()
        i = bisect_right(hs, height)
        return self.get(hs[i - 1]) if i else None

    def delete(self, height: int) -> None:
        self.db.delete(_key(height))

    def prune(self, keep: int) -> None:
        """Drop oldest blocks beyond `keep` (reference db.go Prune)."""
        with trace.span("light.store.prune", keep=keep) as sp:
            hs = self.heights()
            doomed = hs[:-keep] if keep else hs
            for h in doomed:
                self.delete(h)
            self.pruned += len(doomed)
            sp.add(held=len(hs), deleted=len(doomed))
