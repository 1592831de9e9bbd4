import os


def env_flag(name: str) -> bool:
    """Boolean env knob: unset, empty, "0", "false", "no", and "off" are OFF —
    so the natural ways a user spells a disable (FLAG=0, FLAG=no, FLAG=off)
    never accidentally enable the behavior."""
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "no", "off")
