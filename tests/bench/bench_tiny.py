"""Tiny sizes at which the benchmark's cells run on the CPU in a test:
each configuration's family's ``TINY`` widths and a traffic mix cut to a
few short requests."""
from bench import harness

# Limits of the output check at these sizes in bfloat16, set the way the
# cells' own are, from readings on the CPU over seeds 1-6 (the program's
# largest reading, the control's or a fault's smallest):
# rollout: logp_gap 0.0040 / fp8 0.0187, altered token 0.51.
LIMITS = {
    "rollout-longtail": {"wrong_lengths": 0, "logp_gap": 0.01},
}


def tiny_conf(conf: dict, dtype: str, family=None) -> dict:
    """``conf`` at its family's ``TINY`` sizes in ``dtype``; the family is
    found by the file's ``model_type`` unless given."""
    family = family or harness.load_family(conf["model_type"])
    return dict(conf, **family.TINY, torch_dtype=dtype)


def shrink_for(dtype: str, limits: dict | None = None):
    """A ``run_cell`` seam: cut the loaded cell to tiny sizes in ``dtype``
    and, when given, hold it to ``limits``."""
    def shrink(cell):
        cell.conf = tiny_conf(cell.conf, dtype, cell.family)
        t = dict(cell.traffic)
        t.update(prompts=2, group=2, prompt_lengths=[16, 32], slots=4,
                 num_kv_blocks=16, max_seq_len=48, check_requests=2)
        t["response"] = dict(t["response"], max_new=8)
        cell.traffic = t
        if limits is not None:
            cell.limits = dict(limits)
    return shrink
