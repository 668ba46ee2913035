"""The latent (MLA) decode kernel's share of its roofline: the least time
the chip could take for every latent decode launch of the window
(operations and bytes of the layout's ``decode_attention``, one launch per
layer) over the device time of the program ``jit_paged_latent_attention``
in the trace."""

from chipbench import roofline

MODULE = "jit_paged_latent_attention"


def read(rec):
    t = rec.trace.module_s.get(MODULE) if rec.trace else None
    if not t or not rec.decode_launches:
        return None
    need = roofline.launches_seconds(rec.cfg, rec.decode_launches, rec.peaks)
    return 100.0 * need / t
