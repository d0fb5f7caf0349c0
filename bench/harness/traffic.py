"""The general traffic generator and the plain reference of the input
layer.

A traffic mix is a JSON file of parameters (``bench/traffic/<name>.json``).
From it and ``--seed`` this module writes the token corpus as JRecord
shards, and replays, without the program, the batches that the program's
``token_batches`` must yield from them and the file operations each
batch costs.

Document lengths are one fixed multiset (drawn from ``LENGTHS_SEED``)
that every seed shuffles differently, so every seed asks the same work
of the input layer; the tokens themselves come from the seed.

JRecord layout (``.jrec``): the 8-byte magic, then per document
``u64 length | u32 crc32 | payload``; the sidecar ``.idx`` holds
``u64 count`` and one ``u64`` offset per document.
"""
from __future__ import annotations

import os
import struct
import zlib
from dataclasses import dataclass, field
from typing import Dict, Iterator, List

import numpy as np

MAGIC = b"JREC0001"
LENGTHS_SEED = 20260
TOKEN_BYTES = 4                    # int32 tokens
RECORD_HEADER = 12                 # u64 length + u32 crc
SHARD_ORDER_SEED = 0               # token_batches' default shard order


@dataclass
class Corpus:
    shards: List[List[np.ndarray]]          # documents of each shard
    paths: List[str] = field(default_factory=list)

    @property
    def tokens(self) -> int:
        return sum(len(d) for s in self.shards for d in s)


def make_corpus(traffic: dict, vocab_size: int, seed: int) -> Corpus:
    rng_len = np.random.default_rng(LENGTHS_SEED)
    lengths: List[int] = []
    total = 0
    while total < traffic["corpus_tokens"]:
        n = max(traffic["doc_tokens_min"],
                int(rng_len.exponential(traffic["doc_tokens_mean"])))
        lengths.append(n)
        total += n
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths)[rng.permutation(len(lengths))]
    stream = rng.integers(0, vocab_size, int(lengths.sum()), dtype=np.int32)
    docs = np.split(stream, np.cumsum(lengths)[:-1])
    per = -(-len(docs) // traffic["shards"])
    return Corpus([docs[i:i + per] for i in range(0, len(docs), per)])


def write_shards(corpus: Corpus, root: str) -> List[str]:
    os.makedirs(root, exist_ok=True)
    paths = []
    for s, docs in enumerate(corpus.shards):
        path = os.path.join(root, f"tokens_{s:04d}.jrec")
        parts, offsets, off = [MAGIC], [], len(MAGIC)
        for doc in docs:
            payload = doc.tobytes()
            offsets.append(off)
            parts += [struct.pack("<QI", len(payload),
                                  zlib.crc32(payload) & 0xFFFFFFFF), payload]
            off += RECORD_HEADER + len(payload)
        with open(path, "wb") as f:
            f.write(b"".join(parts))
        with open(path + ".idx", "wb") as f:
            f.write(struct.pack(f"<{len(offsets) + 1}Q", len(offsets),
                                *offsets))
        paths.append(path)
    corpus.paths = paths
    return paths


@dataclass
class BatchIO:
    """File operations one ``next(batches)`` call makes."""
    docs: int = 0              # documents read: os.open + 2 os.pread each
    doc_bytes: int = 0         # bytes those preads return
    index_loads: int = 0       # .idx files read through open(): 1 + n reads
    index_reads: int = 0
    index_bytes: int = 0

    def add(self, other: "BatchIO") -> None:
        for k in self.__dataclass_fields__:
            setattr(self, k, getattr(self, k) + getattr(other, k))


def expected_batches(corpus: Corpus, batch: int, seq: int,
                     vocab_size: int) -> Iterator[tuple]:
    """Yields ``(tokens (batch, seq) int32, BatchIO)`` in the order the
    program's ``token_batches`` must: shards in a fresh permutation each
    epoch (default_rng(0)), documents in index order, concatenated and
    cut into batch * seq windows."""
    rng = np.random.default_rng(SHARD_ORDER_SEED)
    need = batch * seq
    buf: List[np.ndarray] = []
    have = 0
    io = BatchIO()
    while True:
        for si in rng.permutation(len(corpus.shards)):
            docs = corpus.shards[si]
            io.index_loads += 1
            io.index_reads += 1 + len(docs)
            io.index_bytes += 8 * (1 + len(docs))
            for doc in docs:
                io.docs += 1
                io.doc_bytes += RECORD_HEADER + TOKEN_BYTES * len(doc)
                buf.append(doc % vocab_size)
                have += len(doc)
                while have >= need:
                    flat = np.concatenate(buf)
                    yield flat[:need].reshape(batch, seq), io
                    io = BatchIO()
                    buf, have = [flat[need:]], len(flat) - need


def io_of(corpus: Corpus, batch: int, seq: int, vocab_size: int,
          calls: range) -> BatchIO:
    """Summed file operations of the ``next`` calls in ``calls``."""
    total = BatchIO()
    for i, (_, io) in enumerate(expected_batches(corpus, batch, seq,
                                                 vocab_size)):
        if i >= calls.stop:
            break
        if i >= calls.start:
            total.add(io)
    return total


def batch_crc(tokens: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(tokens, np.int32).tobytes())


def shard_counters(reports, paths) -> Dict[str, int]:
    """The profiler's record of the input layer, summed over its windows:
    POSIX opens, reads and bytes of the shard files, and the STDIO
    totals (the shards' index files are the only STDIO reads)."""
    wanted = set(paths)
    out = {"posix_opens": 0, "posix_reads": 0, "posix_bytes_read": 0,
           "stdio_reads": 0, "stdio_bytes_read": 0, "stdio_writes": 0,
           "stdio_bytes_written": 0}
    for rep in reports:
        for path, rec in rep.per_file.items():
            if path in wanted:
                c = rec.counters
                out["posix_opens"] += c.get("POSIX_OPENS", 0)
                out["posix_reads"] += c.get("POSIX_READS", 0)
                out["posix_bytes_read"] += c.get("POSIX_BYTES_READ", 0)
        out["stdio_reads"] += rep.stdio.reads
        out["stdio_bytes_read"] += rep.stdio.bytes_read
        out["stdio_writes"] += rep.stdio.writes
        out["stdio_bytes_written"] += rep.stdio.bytes_written
    return out
