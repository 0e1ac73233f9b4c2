"""A pass-through around the program's input pipeline that keeps the first
batches it delivers, so that `correct` can read what the infeed really fed.

``TPUEstimator.fit`` takes any object with ``epoch()`` and
``steps_per_epoch``; this one hands every call on to the pipeline it wraps
(the pump, the lanes and ``sharded_put`` run as they do without it) and holds
a reference to the first ``keep`` batches that ``fit`` consumed: device
arrays, fetched to the host by :meth:`take` outside any timed step.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


class RecordingFeed:
    def __init__(self, pipeline, keep: int):
        self._pipeline = pipeline
        self._keep = keep
        self._epochs: List[list] = []
        self.batches_fed = 0

    @property
    def steps_per_epoch(self) -> int:
        return self._pipeline.steps_per_epoch

    @property
    def stats(self):
        return self._pipeline.stats

    @stats.setter
    def stats(self, value):
        self._pipeline.stats = value

    def epoch(self, *args, prefetch: bool = True, **kwargs):
        gen = self._pipeline.epoch(*args, prefetch=prefetch, **kwargs)
        if not prefetch:            # fit's one sample for the build
            yield from gen
            return
        kept = None
        for batch in gen:
            if self._keep > 0:
                if kept is None:
                    kept = []
                    self._epochs.append(kept)
                kept.append(batch)
                self._keep -= 1
            self.batches_fed += 1
            yield batch

    def take(self) -> List[List[Tuple[np.ndarray, np.ndarray]]]:
        """The kept batches as host arrays, epoch by epoch; forgets them."""
        out = [[(np.asarray(b.x[0]), np.asarray(b.y[0])) for b in ep]
               for ep in self._epochs]
        self._epochs = []
        return out
