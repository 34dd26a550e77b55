"""Container file bytes x 8 over the read set's bases: directory, consensus,
parity and extents together, as the store keeps them on disk."""


def read(m):
    return 8 * m["container_bytes"] / m["corpus_bases"]
