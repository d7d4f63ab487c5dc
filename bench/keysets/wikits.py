"""Key set ``wikits``: synthetic timestamps, shaped after SOSD's
``wiki_ts_200M_uint64`` (Wikipedia edit timestamps; Kipf et al., "SOSD: A
Benchmark for Learned Indexes") but not drawn from it: the file is not in
the repository. Near-linear increments from 1.5e9 with bursty gaps: 30 %
of gaps exponential with mean 2, the rest with mean 50, each plus 1, so
keys are distinct (SOSD's file has duplicates; an index key is unique). A
copy of ``repro.data.datasets.make_wikits``."""
import numpy as np

from yardstick.keysets import unique_pad


def make(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    busy = rng.random(n) < 0.3
    gaps = np.where(
        busy,
        rng.exponential(2.0, n),
        rng.exponential(50.0, n),
    ).astype(np.int64) + 1
    keys = np.cumsum(gaps) + 1_500_000_000
    return unique_pad(keys, n, rng)
