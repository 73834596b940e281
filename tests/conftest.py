"""Test session config: give the CPU backend 8 placeholder devices so the
distributed tests (shard_map MoE dispatch, hierarchical collectives, the
CI-sized dry-run twin) actually execute under the plain ``pytest tests/``
invocation.

8, NOT 512: the smoke tests and kernel tests are written against small
meshes; the 512-device production mesh is exercised only by the dry-run
launcher, which sets its own XLA_FLAGS before any jax import (see
repro/launch/dryrun.py).  A pre-existing XLA_FLAGS is respected.
"""
import contextlib
import os

import pytest

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"


@pytest.fixture
def slice_and_stack():
    """A context in which the model walks a paged pool as it did before the
    pool was threaded through the layers: each layer gets its own layer
    sliced out of the pool (a one-layer pool, whose block ids and trash
    block are the host's), and the layers' new pools are stacked and
    joined back into a fresh array.  The oracle of the in-place path: a
    step traced inside it computes the same tokens and the same pool."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T
    from repro.serve.kv_cache import PagedKVCache

    real = T._scan_layers

    def oracle(layer_fn, h, layer_params, cache, cfg):
        if not isinstance(cache, PagedKVCache):
            return real(layer_fn, h, layer_params, cache, cfg)
        first = cache.layer
        per_layer = isinstance(layer_params, (list, tuple))
        n = (len(layer_params) if per_layer
             else jax.tree_util.tree_leaves(layer_params)[0].shape[0])
        aux = {"moe_aux": jnp.zeros((), jnp.float32),
               "moe_zloss": jnp.zeros((), jnp.float32)}
        ks, vs = [], []
        for i in range(n):
            lp = (layer_params[i] if per_layer
                  else jax.tree_util.tree_map(lambda x: x[i], layer_params))
            one = cache._replace(k=cache.k[first + i][None],
                                 v=cache.v[first + i][None], layer=0)
            h, new, a = layer_fn(lp, h, one)
            aux = jax.tree_util.tree_map(jnp.add, aux, a)
            ks.append(new.k[0])
            vs.append(new.v[0])

        def join(x, new):
            return jnp.concatenate([x[:first], jnp.stack(new), x[first + n:]])

        return h, aux, cache._replace(k=join(cache.k, ks),
                                      v=join(cache.v, vs), layer=first + n)

    @contextlib.contextmanager
    def active():
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(T, "_scan_layers", oracle)
            yield

    return active
