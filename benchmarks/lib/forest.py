"""A forest of the published tree count from a few trained trees, through
the public model text: the `Tree=` blocks repeated, the header's
`tree_sizes` rewritten, every trailer (parameters, the bin-mapper snapshot
that keeps a reloaded model on the device path) kept as it was."""

import re

from .reference import split_model_text


def tile_model_text(text: str, trees: int) -> str:
    head, blocks, tail = split_model_text(text)
    if not blocks:
        raise ValueError("no trees to tile")
    reps = -(-trees // len(blocks))
    bodies = (blocks * reps)[:trees]
    chunks = [f"Tree={i}\n{b}\n\n" for i, b in enumerate(bodies)]
    sizes = " ".join(str(len(c)) for c in chunks)
    head, n = re.subn(r"(?m)^tree_sizes=.*$", "tree_sizes=" + sizes, head)
    if n != 1:
        raise ValueError("model text header has no tree_sizes line")
    return head + "".join(chunks) + tail
