"""A stand-in reference module for an architecture that
bench/references/moe_gqa.py does not draw: the program's DeepSeek-V3
layout at smoke widths (MLA attention, dense prologue layers, a shared
expert).  A test copies it into a checkout as a new file.

It draws whatever tree the engine's layout asks for, counts work with
numbers a test can tell from any real count, and records what the
harness hands it.  Its ``gaps`` only record the sizes and seed they were
given: it is no reference of the model.
"""
import jax
import numpy as np

from bench import weights

DECODE_KERNEL = "paged_mla"

# what the harness handed over and what this module returned
LOG = {"dims": [], "engine_params": [], "gaps": [], "model_flops": [],
       "kernel_work": []}


def dims(cfg):
    """Sizes, and the engine's layout of one layer of each stacked group."""
    from repro.models.params import abstract_params

    tree = abstract_params(cfg)
    one = lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype)  # noqa: E731
    d = {"d_model": cfg.d_model, "vocab_size": cfg.vocab_size,
         "num_layers": cfg.num_layers, "prologue_layers": len(cfg.prologue),
         "prologue": jax.tree.map(one, tree["prologue"]["p0"]),
         "period": jax.tree.map(one, tree["blocks"]["p0"]),
         "top": {k: v for k, v in tree.items()
                 if k not in ("blocks", "prologue")}}
    LOG["dims"].append(d)
    return d


def _draw(tree, key, fan_in):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([
        weights.scale(k, a.shape[0]) if len(a.shape) == 1
        else weights.normal(k, a.shape, fan_in)
        for k, a in zip(keys, leaves)])


def layer(dims, seed, index):
    group = (dims["prologue"] if index < dims["prologue_layers"]
             else dims["period"])
    key = jax.random.fold_in(weights.base_key(seed), index + 1)
    return _draw(group, key, dims["d_model"])


def top(dims, seed):
    key = jax.random.fold_in(weights.base_key(seed), 0)
    return _draw(dims["top"], key, dims["d_model"])


def engine_params(cfg, dims, seed):
    """Prologue layers stack into ``prologue.p0``, the rest into
    ``blocks.p0``."""
    layers = [jax.device_get(layer(dims, seed, i))
              for i in range(dims["num_layers"])]
    n = dims["prologue_layers"]
    stack = lambda ls: jax.tree.map(lambda *xs: np.stack(xs), *ls)  # noqa
    params = dict(top(dims, seed))
    params["prologue"] = {"p0": stack(layers[:n])}
    params["blocks"] = {"p0": stack(layers[n:])}
    LOG["engine_params"].append(params)
    return params


def prefill_flops(dims, n):
    LOG["model_flops"].append(1_000_003 * n)
    return LOG["model_flops"][-1]


def decode_flops(dims, ctx):
    LOG["model_flops"].append(7 * ctx + 1)
    return LOG["model_flops"][-1]


def decode_kernel_work(dims, ctx):
    LOG["kernel_work"].append((11 * ctx, 13 * ctx + 5))
    return LOG["kernel_work"][-1]


def gaps(dims, seed, seqs, control=False):
    LOG["gaps"].append((dims, seed))
    return [np.zeros((len(s),), np.float32) for _, s in seqs]
