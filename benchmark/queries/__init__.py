"""One file a query:

  TABLES                       the tables it reads, by the configuration's names
  build(frames)                the DataFrame, from {table: DataFrame}
  answer(rows)                 collect()'s rows as {group key: exact int}
  reference(tables)            the same from the numpy columns, in int64
  reference_lowp(tables)       the control: the reference in float32
  min_bytes(rows)              the least bytes the device must read, from
                               {table: row count}; None where not reckoned

The references import nothing of the engine."""
