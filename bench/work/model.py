"""Model operations per request, from the published sizes.

Every token a request makes the model process, prompt and output alike,
multiplies the body's weights once (2 operations per weight) and attends
over the tokens before it and itself (4*H*Dh operations per key, per
layer). The tied head runs once per emitted token: on the last prompt
token and on each decode step.
"""


def request_ops(sizes, prompt_len: int, new_tokens: int) -> float:
    head = 2.0 * sizes.d * sizes.vocab
    body = 2.0 * (sizes.matmul_params() - sizes.d * sizes.vocab)
    per_key = 4.0 * sizes.layers * sizes.heads * sizes.d_head
    tokens = prompt_len + new_tokens - 1            # positions processed
    keys = tokens * (tokens + 1) / 2                # sum of (p + 1)
    return tokens * body + keys * per_key + new_tokens * head
