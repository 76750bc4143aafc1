"""Host CPU seconds (user + system) of this process and of the processes a
cell started, read from ``os.times()`` and ``/proc/<pid>/stat``."""

import os

_TICK = os.sysconf("SC_CLK_TCK")


def proc_cpu_s(pid):
    """User + system seconds of a live process; 0.0 once it is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
    except OSError:
        return 0.0
    # fields[0] is the state (field 3); utime and stime are fields 14, 15.
    return (int(fields[11]) + int(fields[12])) / _TICK


def cpu_s(child_pids=()):
    """This process's user + system seconds plus those of ``child_pids``."""
    t = os.times()
    return t.user + t.system + sum(proc_cpu_s(p) for p in child_pids)


def process_age_s():
    """Seconds since this process started, by the kernel's clock."""
    with open(f"/proc/{os.getpid()}/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / _TICK
