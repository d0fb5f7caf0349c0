"""Token pipeline: JRecord document shards -> fixed-length LM batches.

Documents are concatenated and packed into (batch, seq_len) windows.
The model runs over the whole window and the loss predicts its last
seq_len - 1 tokens from the ones before (``models.loss_fn``), so the
model's sequence length is seq_len, as in the dry-run's batch specs.

Sharding is by file round-robin per DP worker.  Each document is read
by index (``JRecordReader.read``: os.open + os.pread), so a profiling
window sees the POSIX reads of the documents its steps consume, even
when the shard was first opened before the window.
"""
from __future__ import annotations

from typing import Iterator, List, Optional

import numpy as np

from repro.data.jrecord import JRecordReader


def token_batches(shard_paths: List[str], batch_size: int, seq_len: int,
                  vocab_size: int, seed: int = 0,
                  repeat: bool = True) -> Iterator[np.ndarray]:
    """Yields int32 (batch_size, seq_len) token windows forever
    (or once if repeat=False)."""
    rng = np.random.default_rng(seed)
    window = seq_len
    buf = np.empty((0,), np.int32)
    epoch = 0
    while True:
        order = rng.permutation(len(shard_paths))
        for si in order:
            reader = JRecordReader(shard_paths[si])
            for i in range(len(reader)):
                doc = np.frombuffer(reader.read(i), np.int32) % vocab_size
                buf = np.concatenate([buf, doc])
                while len(buf) >= batch_size * window:
                    take = buf[:batch_size * window]
                    buf = buf[batch_size * window:]
                    yield take.reshape(batch_size, window).copy()
        epoch += 1
        if not repeat:
            return
