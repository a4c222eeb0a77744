"""Model zoo — the capability contract of the reference's Fluid "book"
(python/paddle/v2/fluid/tests/book/): fit_a_line, recognize_digits,
image_classification (VGG/ResNet), word2vec, understand_sentiment,
recommender, label_semantic_roles, machine_translation + Transformer.

Each module exposes builder functions that append layers to the current
program, mirroring how the book chapters build nets, so user scripts look
identical to the reference's."""

from . import (  # noqa: F401
    ctr,
    fit_a_line,
    image_classification,
    label_semantic_roles,
    recognize_digits,
    recommender,
    sentiment,
    word2vec,
)


# Decoder-only language models the paged engine serves
# (``serving.paged_lm.PagedLMGenerator``): the module named like the
# ``model_type`` of the published configuration gives ``config_from_dict``,
# ``cache_specs`` (per kind of layer a pool, or a pool pair:
# ``cache_spec.CacheSpec``), ``param_shapes`` and ``build_serve_step``.
# What the engine shares is the cache and the flat batch; everything
# between a layer's input and its cache rows is the builder's, kind by
# kind: rotary over part of a head, all of it or none (``afmoe``'s global
# layers carry no position), a norm on every head of the queries and keys
# before they are cached, a gate on the attention's output, norms before
# and after a sub-block.
DECODER_LMS = ("mimo_v2_flash", "deepseek_v3", "afmoe")


def decoder_lm(model_type: str):
    """The builder module of a decoder-only model, by its ``model_type``."""
    import importlib

    if model_type not in DECODER_LMS:
        raise KeyError(f"no decoder-only model of model_type {model_type!r} "
                       f"(known: {sorted(DECODER_LMS)})")
    return importlib.import_module(f"{__name__}.{model_type}")
