"""cache manager: bytes a cached token costs, as allocated, over the layers
that keep every position (the engine's counter ``kv_bytes_per_token``: a
pool's, or a pool pair's, row width a layer).  A latent kind has ONE pool;
a value pool beside it would nearly double this."""


def read(layer):
    if layer.get("kind") != "serve":
        return None
    eng = (layer.get("after") or {}).get("engine") or {}
    value = eng.get("kv_bytes_per_token")
    return None if value is None else float(value)
