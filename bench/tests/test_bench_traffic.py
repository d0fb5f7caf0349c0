"""The traffic generator and the input layer's plain reference agree with
the program's ``token_batches`` batch for batch and syscall for syscall."""
import numpy as np
import pytest

from harness import traffic

TRAFFIC = {"corpus_tokens": 6000, "doc_tokens_mean": 40,
           "doc_tokens_min": 16, "shards": 3}


@pytest.mark.parametrize("batch,seq", [(2, 64), (1, 300)])
def test_expected_batches_and_io_match_the_program(tmp_path, batch, seq):
    from repro.core.runtime import reset_runtime
    from repro.core.session import ProfileSession
    from repro.data.tokens import token_batches

    vocab = 97
    corpus = traffic.make_corpus(TRAFFIC, vocab, seed=2**31 + 9)
    paths = traffic.write_shards(corpus, str(tmp_path / "tok"))
    n = 2 * corpus.tokens // (batch * seq) + 3     # past one epoch
    session = ProfileSession(reset_runtime(), trace=False)
    got = token_batches(paths, batch, seq, vocab)
    for i, (want, io) in enumerate(traffic.expected_batches(
            corpus, batch, seq, vocab)):
        if i == n:
            break
        session.start()
        tokens = next(got)
        rep = session.stop()
        np.testing.assert_array_equal(tokens, want)
        rec = traffic.shard_counters([rep], paths)
        assert rec["posix_opens"] == io.docs
        assert rec["posix_reads"] == 2 * io.docs
        assert rec["posix_bytes_read"] == io.doc_bytes
        assert rec["stdio_reads"] == io.index_reads
        assert rec["stdio_bytes_read"] == io.index_bytes
    total = traffic.io_of(corpus, batch, seq, vocab, range(1, 4))
    parts = [io for i, (_, io) in zip(range(4), traffic.expected_batches(
        corpus, batch, seq, vocab)) if i >= 1]
    assert total.docs == sum(p.docs for p in parts)


def test_every_seed_asks_the_same_work():
    a = traffic.make_corpus(TRAFFIC, 50, seed=1)
    b = traffic.make_corpus(TRAFFIC, 50, seed=3_000_000_000)
    lengths = lambda c: sorted(len(d) for s in c.shards for d in s)  # noqa: E731
    assert lengths(a) == lengths(b)
    assert a.tokens >= TRAFFIC["corpus_tokens"]
    first = lambda c: c.shards[0][0][:8].tolist()  # noqa: E731
    assert first(a) != first(b)
