"""Paper Table 1: clustering cost (SSE) — standard k-means vs equal /
unequal subclustering at 6 subclusters, 6x compression.

Iris/Seeds are statistically matched synthetic surrogates (the UCI files are
not downloadable offline — see DESIGN.md §8); the *relative* claim (sampled
within a few % of full k-means) is what this table validates.  The paper
reports 133 -> 138 (iris) and 187 -> 191 (seeds): +3.8% / +2.1%.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_clustering import workload_spec
from repro.core import relative_error, sampled_kmeans, standard_kmeans
from repro.data.synthetic import surrogate_iris, surrogate_seeds


def run(csv):
    rows = []
    for name, (x, y), k in [("iris", surrogate_iris(), 3),
                            ("seeds", surrogate_seeds(), 3)]:
        xj = jnp.asarray(x)
        t0 = time.perf_counter()
        full = standard_kmeans(xj, k, iters=40, key=jax.random.PRNGKey(0))
        jax.block_until_ready(full.sse)
        t_full = time.perf_counter() - t0
        csv(f"table1/{name}/standard_kmeans", t_full * 1e6,
            f"sse={float(full.sse):.2f}")
        for scheme in ("equal", "unequal"):
            spec = workload_spec(name, scheme=scheme)
            t0 = time.perf_counter()
            s = sampled_kmeans(xj, k, spec=spec, key=jax.random.PRNGKey(0))
            jax.block_until_ready(s.sse)
            dt = time.perf_counter() - t0
            rel = relative_error(float(s.sse), float(full.sse))
            csv(f"table1/{name}/{scheme}_6sub_6x", dt * 1e6,
                f"sse={float(s.sse):.2f};rel_err={rel:+.3%};"
                f"paper_rel=+3.8%/+2.1%")
            rows.append((name, scheme, float(s.sse), rel))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
