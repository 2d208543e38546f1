"""What `drivers/serve_closed_sessions.py` takes from the program beside
`lib/program.py` and `lib/program_stateful.py` (neither may be edited):
the path statistics of the latent-attention walk. Imports of `paddle_tpu`
happen inside the functions."""
from __future__ import annotations


def latent_path_stats(reset=False):
    """`{"decode": {...}, "chunk": {...}}`: which form of the latent
    decode walk, and which form of a prefill chunk, was traced."""
    from paddle_tpu.ops import paged_attention as pa

    if reset:
        pa.reset_latent_path_stats()
    return {"decode": dict(pa.LATENT_PATH_STATS),
            "chunk": dict(pa.LATENT_CHUNK_STATS)}
