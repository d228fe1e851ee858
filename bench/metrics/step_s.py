"""Seconds per denoise step: the host-clock wall of the whole requests
served in the window, from the first submit until the last latent was
returned and blocked on, divided by the steps they completed."""


def read(rec):
    return rec["step_s"] if rec["steps"] else None
