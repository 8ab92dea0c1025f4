"""Host-side tokenizer throughput benchmark: native C core vs python
oracle (paddle_tpu/text/tokenizer.py; the faster_tokenizer analog).

CPU numbers are the CORRECT kind of evidence here — tokenization is
host-side work in both the reference and this framework — so this tool
prints its row, labelled host_side, and writes no file.

Run: python tools/bench_tokenizer.py
"""
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def _make_vocab(n_words=8000, seed=0):
    """BERT-shaped vocab: specials, whole words, ##-continuations."""
    R = np.random.RandomState(seed)
    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]
    alphabet = "abcdefghijklmnopqrstuvwxyz"
    seen = set(vocab)
    while len(vocab) < n_words:
        w = "".join(R.choice(list(alphabet), R.randint(2, 9)))
        for cand in (w, "##" + w[:max(1, len(w) // 2)]):
            if cand not in seen:
                seen.add(cand)
                vocab.append(cand)
    return vocab[:n_words]


def _make_text(vocab, n_words=200_000, seed=1):
    R = np.random.RandomState(seed)
    words = [v for v in vocab if not v.startswith("##") and v[0] != "["]
    # half in-vocab words, half random (exercises the UNK/continuation path)
    alphabet = "abcdefghijklmnopqrstuvwxyz"
    out = []
    for _ in range(n_words):
        if R.rand() < 0.5:
            out.append(words[R.randint(len(words))])
        else:
            out.append("".join(R.choice(list(alphabet), R.randint(2, 12))))
    return " ".join(out)


def _time_encode(tok, text, repeats=3):
    best = float("inf")
    ids = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        ids = tok.encode(text)
        best = min(best, time.perf_counter() - t0)
    return best, ids


def main():
    from paddle_tpu.text.tokenizer import WordPieceTokenizer

    vocab = _make_vocab()
    text = _make_text(vocab)
    n_bytes = len(text.encode("utf-8"))

    native = WordPieceTokenizer(vocab, use_native=True)
    python = WordPieceTokenizer(vocab, use_native=False)

    t_native, ids_n = _time_encode(native, text)
    t_python, ids_p = _time_encode(python, text)
    assert list(ids_n) == list(ids_p), "native/python parity violated"

    row = {
        "host_side": True,
        "corpus_mb": n_bytes / 1e6,
        "tokens": len(ids_n),
        "native_mb_per_s": n_bytes / 1e6 / t_native,
        "python_mb_per_s": n_bytes / 1e6 / t_python,
        "speedup_native_over_python": t_python / t_native,
    }
    print(f"[tokenizer] {n_bytes / 1e6:.1f}MB corpus, {len(ids_n)} tokens: "
          f"native {row['native_mb_per_s']:.1f}MB/s vs python "
          f"{row['python_mb_per_s']:.1f}MB/s "
          f"({row['speedup_native_over_python']:.1f}x)", file=sys.stderr)
    print(json.dumps(row))


if __name__ == "__main__":
    main()
