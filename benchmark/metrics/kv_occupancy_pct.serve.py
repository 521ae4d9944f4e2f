"""Blocks of the KV pool in use over the usable blocks
(``KVCachePool.occupancy``, what ``gauge/serve/kv_occupancy`` shows), mean
of the generator's samples over the window."""


def read(run: dict):
    value = run.get("kv_occupancy")
    return None if value is None else 100.0 * value
