"""Operations and bytes a vision tower's encode needs, from shapes alone, and
the work of a cell's traced stretch.  The algorithm's minimum, as in
``roofline.py``: one image of ``N`` patches through the tower
(``vision_config``: width ``C``, ``L`` layers, ``H`` heads of ``d``, MLP
width ``I``, patches of ``3 p p`` values), the 2 x 2 merger and the projector
into the language model's width ``D``.

* products a patch: the patch embedding ``2 (3 p p) C``; a layer ``2 C (3 C)
  + 2 C C + 2 C I + 2 I C``;
* attention a layer: every patch sees every patch of its image, ``4 N^2 d
  H`` (scores and values, 2 each);
* the projector a row of four patches: ``2 (4 C)^2 + 2 (4 C) D``.

Bytes: the tower's and the projector's weights once a dispatch (a dispatch
encodes one image), the pixels in and the rows out.  The interpolation of the
position table, the norms, the rotary and the activations between layers are
left out, so the count errs low.  A padded bucket's padding is not work.
"""

import roofline


def weights(vc: dict, hidden: int) -> int:
    """Parameters of tower, merger and projector (matrices and their biases,
    the position table, the norms)."""
    c, i, l = vc["hidden_size"], vc["intermediate_size"], vc["num_hidden_layers"]
    patch = 3 * vc["patch_size"]**2
    m = vc["merge_kernel_size"][0] * vc["merge_kernel_size"][1]
    layer = c * 3 * c + 3 * c + c * c + c + c * i + i + i * c + c + 4 * c
    tower = patch * c + c + vc["init_pos_emb_height"] * vc["init_pos_emb_width"] * c + l * layer + 2 * c
    projector = 2 * c + (m * c)**2 + m * c + m * c * hidden + hidden
    return tower + projector


def encode_call(n_patches: int, vc: dict, hidden: int, elem_bytes: int = 2):
    """One image of ``n_patches`` patches: (FLOPs, bytes)."""
    c, i, l, heads = vc["hidden_size"], vc["intermediate_size"], vc["num_hidden_layers"], vc["num_attention_heads"]
    patch = 3 * vc["patch_size"]**2
    m = vc["merge_kernel_size"][0] * vc["merge_kernel_size"][1]
    a_patch = 2 * patch * c + l * (2 * c * 3 * c + 2 * c * c + 4 * c * i)
    pairs = l * 4 * n_patches**2 * (c // heads) * heads
    a_row = 2 * (m * c)**2 + 2 * m * c * hidden
    flops = n_patches * a_patch + pairs + (n_patches // m) * a_row
    nbytes = elem_bytes * (weights(vc, hidden) + n_patches * patch + (n_patches // m) * hidden)
    return flops, nbytes


PROGRAM = "jit_ds_vit_p"   # the tower's programs on the trace's ``XLA Modules`` line: jit_ds_vit_p4096


def program_events(reduced: dict) -> list:
    """(bucket, seconds) of each run of a tower's program in the trace, in
    order; [] where the reduced trace holds no modules line or no such run."""
    out = []
    for name, start, end, _ in (reduced.get("modules") or []):
        if name.startswith(PROGRAM):
            out.append((int(name[len(PROGRAM):].split("(")[0]), end - start))
    return out


def traced_encodes(run: dict):
    """The encode records (``StepAnatomy.encodes``) of the runs of the tower's
    programs the trace holds: the device runs what was dispatched in order, so
    they are the last ``k`` records dispatched by the trace's end, give or
    take those still in flight when it stopped; the alignment is the one at
    which the buckets agree.  None where the program keeps no such records
    (a parent of the PR that brought them) or nothing agrees."""
    try:
        from deepspeed_tpu.telemetry import recorders
    except ImportError:
        return None
    trace = run.get("reduced")
    events = program_events(trace) if trace else []
    if not events or not run.get("ticks"):
        return None
    end = run["ticks"][-1][1]
    buckets = [b for b, _ in events]
    for rec in recorders():
        rows = [r for r in getattr(rec, "encodes", ()) if r["ts"] <= end + 1.0]
        for drop in range(0, 6):
            tail = rows[len(rows) - drop - len(events):len(rows) - drop]
            if len(tail) == len(events) and [r["vit_patches_padded"] for r in tail] == buckets:
                return tail
    return None


def traced_work(run: dict):
    """Least seconds by the roofline for the traced stretch's encodes: an
    encode is bound by its operations or by its bytes, so the encodes' least
    times are added."""
    rows = traced_encodes(run)
    if not rows or run.get("peak") is None:
        return None
    vc, hidden = run["config"]["vision_config"], run["config"]["hidden_size"]
    return sum(roofline.least_time_s(*encode_call(r["vit_patches_real"], vc, hidden), run["peak"]) for r in rows)


def program_seconds(reduced: dict) -> float:
    return sum(s for _, s in program_events(reduced))
