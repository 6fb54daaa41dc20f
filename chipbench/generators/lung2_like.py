"""Structural analogue of SuiteSparse lung2's lower triangle, with
diagonally dominant values from the seed.

Configuration keys: `scale` (share of lung2's 109,460 rows),
`pattern_seed` (the pattern, fixed by the configuration) and
`values_seed_offset` (keeps the values' draws apart from the right-hand
sides').  The pattern is the one `repro.sparse.generators.lung2_like(
scale, seed=pattern_seed)` builds.
"""
from __future__ import annotations

import numpy as np

from chipbench.matrices import dominant_values, from_level_profile, spread


def pattern(scale: float, pattern_seed: int):
    """arXiv:2206.05843 Table I's lung2 profile: 479 levels, 453 of them
    with 2 rows, between 26 fat ones; values all 1."""
    rng = np.random.default_rng(pattern_seed)
    n_target = int(round(109_460 * scale))
    thin_levels, fat_levels = 453, 26
    fat_sizes = spread(n_target - 2 * thin_levels, fat_levels)
    runs = spread(thin_levels, fat_levels - 1)
    sizes, kinds = [], []
    for i in range(fat_levels):
        sizes.append(fat_sizes[i])
        kinds.append("fat")
        if i < fat_levels - 1:
            sizes.extend([2] * runs[i])
            kinds.extend(["thin"] * runs[i])
    kinds = np.asarray(kinds)

    def indegree(rng, lvl, m):
        if kinds[lvl] == "thin":
            return np.ones(m, dtype=np.int64)
        return 1 + (rng.random(m) < 0.50).astype(np.int64)

    def distance(rng, lvl, k):
        return 1 + rng.geometric(0.8, size=k)

    return from_level_profile(sizes, indegree, distance, rng)


def build(config: dict, seed: int):
    return dominant_values(
        pattern(config["scale"], config["pattern_seed"]),
        np.random.default_rng([seed, config["values_seed_offset"]]))
