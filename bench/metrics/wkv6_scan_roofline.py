"""Share of the roofline of the WKV6 recurrence kernels (wkv6_scan*)."""
from roofline import family_share


def read(run):
    return family_share(run, "wkv6_scan")
