"""Message sizes listed in bytes (``sizes_bytes``), as elements of
``dtype``."""


def sizes(config: dict, itemsize: int) -> list[int]:
    out = [int(b) // itemsize for b in config["sizes_bytes"]]
    if min(out) < 1:
        raise ValueError(f"sizes_bytes {config['sizes_bytes']} holds a size "
                         f"under one {itemsize}-byte element")
    return out
