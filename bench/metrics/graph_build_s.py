"""Host clock around the program's graph build (``from_edge_list``:
symmetrize, deduplicate, CSR and CSC) and its transfer to the chip."""


def read(run):
    return run.graph_build_s
