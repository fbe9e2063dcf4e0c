"""A scope's share of its roofline: the least time the chip could take for
what the scope's algorithm needs in one step (the larger of its operations
over the bf16 peak and its bytes over the memory's peak; the counts are
the family's, ``ctx["work"]["scopes"]``) over the device time of the
scope, forward and ``.bwd``, in one traced step, %. Recomputation is in
the measured time and not in the counts, so the share cannot pass 100."""

from benchmarks import tracered


def share(ctx, scope: str):
    need = ((ctx.get("work") or {}).get("scopes") or {}).get(scope)
    ms = tracered.scope_ms_per_batch(ctx.get("trace"), (scope,))
    if not need or not ms:
        return None
    peaks = ctx["peaks"]
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
