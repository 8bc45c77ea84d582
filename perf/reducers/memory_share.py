"""Peak bytes in use over the allocator's limit, on the fullest device,
in per cent. Nothing to read where the backend keeps no statistics."""


def reduce(obs):
    stats = obs.get("memory")
    if not stats:
        return None
    return 100.0 * max(s["peak_bytes_in_use"] / s["bytes_limit"] for s in stats)
