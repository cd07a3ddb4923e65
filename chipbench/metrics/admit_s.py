"""Host seconds of admission with the pinned geometry: ``MatrixRegistry.admit``
(hash, partition, tile build, staging), or for PageRank the transition
matrix, its tile build and staging."""


def read(name, run):
    return run.admit_s
