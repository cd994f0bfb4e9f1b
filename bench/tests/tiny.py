"""The benchmark's cells cut to a size the CPU interprets in seconds:
the same files and code paths, fewer streams and shorter chunks."""
import time

from bench.lib.harness import Cell, run_cell


def tiny_cell(name: str) -> Cell:
    cell = Cell(name, staged=True)
    cell.wl["params"]["warm_seconds"] = 0.3
    cell.wl["params"]["drain_seconds"] = 30.0
    cell.wl["trace_seconds"] = 0.5
    if name.startswith("fleet"):
        cell.cfg.update(streams=128, buckets=[128], history=16, chunk_t=16,
                        rate_hz=20.0)
    else:
        cell.cfg.update(streams=128, buckets=[128], chunk_t=64)
        cell.cfg["signal"].update(recordings=4, length=1024)
        cell.wl["params"].update(backlog=128)
    return cell


def run_tiny(name: str, *, trace: bool = False, seconds: float = 1.5,
             seed: int = 2 ** 31 + 7, control: bool = False) -> dict:
    return run_cell(tiny_cell(name), seed, seconds, trace,
                    time.perf_counter(), control=control, interpret=True)
