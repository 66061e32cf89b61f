"""Operations and bytes of the work the algorithm needs, at the rows' real
lengths, one file per kernel or program.  Each takes `lengths`, the cached
token counts of the live rows before the step, and `shape`, the sizes
`run.py` derives from the configuration (`shape()` there)."""
