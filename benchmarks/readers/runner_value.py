"""A number the cell's runner took itself and hands over in a table of its
own: `data[table][key]`. For what no reader can take from the process at
the run's end: what one of the program's counters gained between the
measured window's first step and its end (`window_counters`; a cell whose
window is SUPPOSED to count something, as a recovery that compiles or reads
the cache inside it, needs the difference, and a warm recovery reads 0,
which is a reading), and what set-up had spent by the window's first step
where the process goes on building and compiling after it
(`setup_seconds`). A runner that hands no such table, or a key it did not
read: nothing to read.
"""


def read(data: dict, *, table: str, key: str) -> float | None:
    value = (data.get(table) or {}).get(key)
    return None if value is None else float(value)
