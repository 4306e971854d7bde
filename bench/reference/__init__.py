"""Plain references that decide `correct`.  They import nothing of the
program under test and take nothing it made: each builds its own inputs
from the seed, as the benchmark gave them to the program."""
