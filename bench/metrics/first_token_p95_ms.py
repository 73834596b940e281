"""first_token_p95_ms: p95 over every request due in the measured window of
first-token time - due time, in ms (numpy's linear percentile, the
arithmetic of the end-to-end ``ttft_p95_ms``).  In a closed loop it is the
scheduler's admission delay: a client sends its next request when its last
one finishes, and requests that finish on the same tick are prefilled one
per tick."""
import numpy as np


def read(ctx, phase):
    if not ctx.ttft:
        return None
    return float(np.percentile(ctx.ttft, 95))
