"""Shape buckets of the serving engine.

``_bucket`` rounds a batch size or a token count up to a power of two, so
that the jit cache holds one program per bucket and not one per value;
``PROMPT_BUCKETS`` are the edges of the prompt-length histogram. Their one
user in the package is ``inference/continuous.py``. They live in a module of
their own, under this name, because the on-chip benchmark imports ``_bucket``
from here (``chipbench/arch/dense_gqa.py``) to warm the shapes the engine
will ask for, and a PR that edits the engine may not edit the benchmark
(ROADMAP D2 moves them once the engine warms a traffic mix by itself).
"""

from __future__ import annotations

from serverless_learn_tpu.analysis import jitcheck


@jitcheck.bucket
def _bucket(n: int, floor: int = 8) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


# Prompt-length histogram buckets (slt_request_prompt_tokens): prompts
# span tokens-to-books, unlike the batch-size-shaped SIZE_BUCKETS.
PROMPT_BUCKETS = (1, 4, 16, 64, 256, 1024, 4096, 16384)
