"""gap_p90_ms.<cell> (ms) - layer: server step. The 90th percentile of the
gaps between a request's successive tokens (``gap_p99_ms`` says which gaps),
in a cell that does not hold it as its end-to-end metric.

``serve-pythia-1b4-chat`` (PR 45): at 0.8 x its knee three to four requests
decode at a time, 5 % of steps carry a chunk or an admission, and a plain
step is 0.45 ms longer for every further live slot. The 90th gap then lies
on the shoulder between the plain steps of a fuller moment and the first
chunk steps, and where it lands follows the seed's arrivals: 8.47-10.01 ms
over twelve seeds, 6.6 % by the driver's measure against a bound of 4 %
(PERF.md section 4). The cell is judged on the median gap; this stays beside
``gap_p99_ms`` to show the tail."""


def read(record):
    return record.get("end_to_end", {}).get("gap_p90_ms")
