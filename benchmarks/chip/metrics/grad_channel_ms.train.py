"""Device time per window step of the gradient channel (8-bit stochastic
gradients with error feedback: train/channels.py, precision/gradcomp.py):
the operations of ``train_step`` under the ``grad_channel`` scope, over the
window's steps (programs.py)."""


def read(rec):
    t = rec.get("train")
    sc = (rec.get("trace") or {}).get("scopes", {}).get("train_step", {})
    if not t or not t["steps"] or not sc.get("grad_channel"):
        return None
    return 1e3 * sc["grad_channel"] / t["steps"]
