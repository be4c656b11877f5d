"""The yardstick's own code: nothing here imports the program under test
except ``lib/harness.py``, and nothing in the program imports this."""
