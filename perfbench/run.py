#!/usr/bin/env python3
"""python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one benchmark cell, in one process, on the machine it is started
on. The last line of standard output is the result object. Exits non-zero
and prints no result where JAX finds no TPU or fewer chips than the cell
asks for."""

import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from perfbench import core

    sys.exit(core.main(t_start=T_START))
