"""The benchmark's yardstick: data generation, the table of peaks, work
counts, trace reduction, the plain references and the comparison that
decides ``correct``. Nothing here imports the program."""
