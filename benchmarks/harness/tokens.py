"""Packed token sequences from a seed, as a pre-training job's data loader
hands them to ``Estimator.fit``: documents of log-normal length, each ended by
an EOS id, concatenated and cut into sequences of one fixed length with no
padding; ids Zipf-distributed over the configuration's vocabulary. And the
check that what the infeed delivered is those sequences.

A traffic file of this kind::

    {"kind": "token_sequences", "sequence_length": 8192, "sequences": 16,
     "wire_dtype": "uint16", "eos_id": 0,
     "doc_length": {"median": 512, "sigma": 1.2, "min": 16, "max": 8192},
     "zipf_s": 1.0, "shuffle": true}
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def make_sequences(traffic: dict, vocab_size: int, seed: int) -> np.ndarray:
    """(sequences, sequence_length) ids in the traffic's wire dtype. The same
    seed gives the same data."""
    rng = np.random.default_rng(seed)
    n, s = int(traffic["sequences"]), int(traffic["sequence_length"])
    dtype = np.dtype(traffic["wire_dtype"])
    if vocab_size - 1 > np.iinfo(dtype).max:
        raise ValueError(f"ids up to {vocab_size - 1} do not fit {dtype}")
    eos = int(traffic["eos_id"])
    doc = traffic["doc_length"]
    # every id but EOS, the r-th most frequent with probability ~ r**-s
    words = np.array([i for i in range(vocab_size) if i != eos])
    p = np.arange(1, len(words) + 1, dtype=np.float64) ** -float(
        traffic["zipf_s"])
    stream = words[rng.choice(len(words), size=n * s, p=p / p.sum())]
    at = 0
    while at < n * s:
        length = int(np.clip(np.round(rng.lognormal(
            np.log(doc["median"]), doc["sigma"])), doc["min"], doc["max"]))
        at += length
        if at <= n * s:
            stream[at - 1] = eos           # a document's last token
    return stream.reshape(n, s).astype(dtype)


def count_bad_rows(data: np.ndarray, fed: List[List]) -> Dict[str, int]:
    """Rows of the fed batches (by epoch, each batch ``(x, y)``) that are not
    one of the data's sequences, whose labels differ from their inputs, or
    that repeat a sequence within an epoch."""
    index = {row.tobytes(): i for i, row in enumerate(data)}
    rows = bad = 0
    for epoch in fed:
        seen = set()
        for x, y in epoch:
            for row, label in zip(np.asarray(x), np.asarray(y)):
                rows += 1
                i = index.get(row.astype(data.dtype).tobytes())
                if i is None or i in seen or not np.array_equal(row, label):
                    bad += 1
                else:
                    seen.add(i)
    return {"rows": rows, "bad": bad}
