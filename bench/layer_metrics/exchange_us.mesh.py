"""Device 0's time in collective operations per round of the drains
across chips (us/round), from the device trace."""

from bench.readers import first_device, per_round_us


def read(ctx):
    dev = first_device(ctx)
    if dev is None or dev["class_n"].get("collective", 0) == 0:
        return None
    return per_round_us(dev["class_s"]["collective"], ctx)
