-- define [RC1] = uniform_int(20000, 80000)
-- define [RC2] = uniform_int(15000, 60000)
-- define [RC3] = uniform_int(10000, 50000)
-- define [RC4] = uniform_int(5000, 40000)
-- define [RC5] = uniform_int(1000, 30000)
SELECT CASE WHEN (SELECT COUNT(*) FROM store_sales
                  WHERE ss_quantity BETWEEN 1 AND 20) > [RC1]
            THEN (SELECT AVG(ss_ext_discount_amt) FROM store_sales
                  WHERE ss_quantity BETWEEN 1 AND 20)
            ELSE (SELECT AVG(ss_net_paid) FROM store_sales
                  WHERE ss_quantity BETWEEN 1 AND 20) END AS bucket1,
       CASE WHEN (SELECT COUNT(*) FROM store_sales
                  WHERE ss_quantity BETWEEN 21 AND 40) > [RC2]
            THEN (SELECT AVG(ss_ext_discount_amt) FROM store_sales
                  WHERE ss_quantity BETWEEN 21 AND 40)
            ELSE (SELECT AVG(ss_net_paid) FROM store_sales
                  WHERE ss_quantity BETWEEN 21 AND 40) END AS bucket2,
       CASE WHEN (SELECT COUNT(*) FROM store_sales
                  WHERE ss_quantity BETWEEN 41 AND 60) > [RC3]
            THEN (SELECT AVG(ss_ext_discount_amt) FROM store_sales
                  WHERE ss_quantity BETWEEN 41 AND 60)
            ELSE (SELECT AVG(ss_net_paid) FROM store_sales
                  WHERE ss_quantity BETWEEN 41 AND 60) END AS bucket3,
       CASE WHEN (SELECT COUNT(*) FROM store_sales
                  WHERE ss_quantity BETWEEN 61 AND 80) > [RC4]
            THEN (SELECT AVG(ss_ext_discount_amt) FROM store_sales
                  WHERE ss_quantity BETWEEN 61 AND 80)
            ELSE (SELECT AVG(ss_net_paid) FROM store_sales
                  WHERE ss_quantity BETWEEN 61 AND 80) END AS bucket4,
       CASE WHEN (SELECT COUNT(*) FROM store_sales
                  WHERE ss_quantity BETWEEN 81 AND 100) > [RC5]
            THEN (SELECT AVG(ss_ext_discount_amt) FROM store_sales
                  WHERE ss_quantity BETWEEN 81 AND 100)
            ELSE (SELECT AVG(ss_net_paid) FROM store_sales
                  WHERE ss_quantity BETWEEN 81 AND 100) END AS bucket5
FROM reason
WHERE r_reason_sk = 1
