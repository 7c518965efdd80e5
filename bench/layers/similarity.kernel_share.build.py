"""Share of the similarity pass's computed half-edges that a Pallas lane of
``bucket_probe`` computed (``similarity.sigma_kernel`` over
``similarity.sigma_computed``), in %: 100 where every σ group runs the
kernel, less where groups run the jnp ref lane. A program without the
kernel counter gives nothing."""


def read(r):
    if "similarity.sigma_kernel" not in r.counters:
        return None
    done = r.counter("similarity.sigma_computed")
    return None if not done else 100.0 * r.counter(
        "similarity.sigma_kernel") / done
