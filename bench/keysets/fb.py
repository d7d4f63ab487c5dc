"""Key set ``fb``: synthetic ids in heavy-tailed clusters, shaped after
SOSD's ``fb_200M_uint64`` (Facebook user ids; Kipf et al., "SOSD: A
Benchmark for Learned Indexes") but not drawn from it: the file is not in
the repository. Ids are allocated in bursts, locally dense and globally
sparse: ``n // 4096`` cluster centres uniform over [0, 2**52), Pareto(1.2)
cluster sizes, offsets uniform within 2**24 of a centre. A copy of
``repro.data.datasets.make_fb``."""
import numpy as np

from yardstick.keysets import MAX_KEY, unique_pad


def make(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n_clusters = max(64, n // 4096)
    centers = np.sort(rng.integers(0, MAX_KEY, n_clusters))
    sizes = rng.pareto(1.2, n_clusters) + 1
    sizes = np.maximum((sizes / sizes.sum() * n).astype(np.int64), 1)
    offs = rng.integers(0, 1 << 24, size=int(sizes.sum()))
    reps = np.repeat(centers, sizes)
    return unique_pad(reps + offs[: len(reps)], n, rng)
