"""Gradient buckets as PyTorch DDP cuts them: ``parameters`` elements of
``dtype`` in full buckets of ``bucket_cap_mb`` (MiB, as DDP reads it) and
the remainder last, in the order DDP syncs them."""

MiB = 1 << 20


def sizes(config: dict, itemsize: int) -> list[int]:
    cap = int(config["bucket_cap_mb"] * MiB) // itemsize
    full, last = divmod(int(config["parameters"]), cap)
    return [cap] * full + ([last] if last else [])
