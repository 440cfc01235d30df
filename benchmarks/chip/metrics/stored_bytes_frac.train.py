"""Bytes the process passed to write(2) (``/proc/self/io`` ``wchar``) from
the window's start until its saves had landed, over the live state bytes
of those saves: every level, shard, parity file and manifest."""


def read(run):
    return run.read.get("stored_bytes_frac")
