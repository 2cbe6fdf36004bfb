"""Model step: device time per decode call of the operations whose scope
path holds ``attn_core`` (the decode attention kernel and its wrapper's
transposes; a fusion counts under its root's scope)."""
import spans


def read(ctx):
    calls, scopes = spans.decode_scopes(ctx.events)
    if not calls or not scopes or "attn_core" not in scopes:
        return None
    return scopes["attn_core"] / calls / 1e6
