"""model: device self time per step under the four norms of every block
(``attn_norm``, ``attn_post_norm``, ``mlp_norm``, ``mlp_post_norm``) and
``norm_f`` of a looped four-norm stack (ray_tpu/models/ouro.py), both passes
and the recomputed one: 4 x 32 + 4 norm passes forward a step at 8 layers and
4 passes, bytes and not operations. None for a step without post-norms. Moves
step_ms_p90."""


def read(run):
    from benchlib import ouro_trace
    return ouro_trace.sandwich_norm_ms_per_step(run)
